"""Bytes a decode step must move (the weights outside the experts once, each touched expert's, the K and V of every resident row in the attention layers, every running slot's convolution state read and written) at the peak bandwidth over the decode executable's device time a step: the share of the whole step."""
from chipbench import families, phases


def read(run):
    c = run.get("counters", {})
    if not run.get("peaks") or not run.get("decode_steps") \
            or "moe_experts_touched" not in c:
        return None
    family = families.of(run["cfg"])
    nbytes = family.decode_step_bytes(
        run["cfg"], run["decode_steps"], c["moe_experts_touched"],
        family.kv_rows_read(run["cfg"], run["decode_context_tokens"]),
        run["occupancy_sum"])
    return family.share_of_least(
        run, phases.DECODE, None,
        nbytes / run["peaks"]["hbm_bytes_per_s"], "decode_step")
