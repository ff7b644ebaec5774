"""K and V of the rows the window's decode steps kept (read once; 32 heads x 128 x 2 x 2 operations a row) at the chip's peaks over the decode executable's device time under sparse_gather and sparse_attention, both sides a call."""
from chipbench import families, phases


def read(run):
    rows = run.get("counters", {}).get("dsa_rows_selected_decode")
    if not rows:
        return None
    family = families.of(run["cfg"])
    return family.roofline_share(
        run, phases.DECODE, ("sparse_gather", "sparse_attention"),
        *family.sparse_attention_cost(run["cfg"], rows), "sparse_attention")
