"""The selected latent rows of the window's decode steps (read once; 64 heads x (576 + 512) x 2 operations a row) at the chip's peaks over the decode executable's device time under sparse_gather and sparse_attention."""
from chipbench import costs, families, phases

SCOPES = ("sparse_gather", "sparse_attention")


def read(run):
    a = phases.of_run(run)
    rows = run.get("counters", {}).get("dsa_rows_selected_decode")
    if not a or not run.get("peaks") or not rows:
        return None
    seconds = sum(v for k, v in a["by_scope"].get(phases.DECODE, {}).items()
                  if any(s in k.split("/") for s in SCOPES))
    if not seconds:
        return None
    flops, nbytes = families.of(run["cfg"]).sparse_attention_cost(
        run["cfg"], rows)
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    run.setdefault("notes", {})["sparse_attention"] = {
        "bound": bound, "seconds": seconds, "least_s": least}
    return 100.0 * least / seconds
