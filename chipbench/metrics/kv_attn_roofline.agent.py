"""K and V bytes a decode step has to read (the two attention layers, every resident row) and the scores' operations at the chip's peaks over the decode executable's device time a step under full_attention."""
from chipbench import costs, families, phases


def read(run):
    if not run.get("peaks") or not run.get("decode_context_tokens"):
        return None
    family = families.of(run["cfg"])
    flops, nbytes = family.kv_attention_cost(run["cfg"], family.kv_rows_read(
        run["cfg"], run["decode_context_tokens"]))
    least, bound = costs.roofline_seconds(flops, nbytes, run["peaks"])
    return family.share_of_least(run, phases.DECODE, ("full_attention",),
                                 least, "kv_attention", bound)
