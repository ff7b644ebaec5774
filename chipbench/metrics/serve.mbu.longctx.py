"""Bytes a decode step must read (the weights outside the experts once, each touched expert's, the index key of every row scored, K and V of every row kept) at the peak bandwidth over the decode executable's device time a step: the share of the whole step."""
from chipbench import families, phases


def read(run):
    c = run.get("counters", {})
    if not run.get("peaks") or not run.get("decode_steps") \
            or "dsa_rows_selected_decode" not in c:
        return None
    family = families.of(run["cfg"])
    nbytes = family.decode_step_bytes(
        run["cfg"], run["decode_steps"], c.get("moe_experts_touched", 0),
        c.get("dsa_rows_scored_decode", 0), c["dsa_rows_selected_decode"])
    return family.share_of_least(
        run, phases.DECODE, None,
        nbytes / run["peaks"]["hbm_bytes_per_s"], "decode_step")
