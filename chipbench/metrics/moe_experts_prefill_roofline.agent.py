"""The experts the window's chunks touched (weights read once a chunk and layer) and their real rows' assignments at the chip's peaks over the prefill-chunk executable's device time under experts_routed."""
from chipbench import families, phases


def read(run):
    return families.of(run["cfg"]).routed_experts_roofline(
        run, phases.PREFILL_CHUNK, "prefill", "moe_experts_touched_prefill")
