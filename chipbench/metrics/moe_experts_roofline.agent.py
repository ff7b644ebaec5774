"""The touched experts' weights (read once a step and layer) and the assignments' 3 x 2048 x 1536 x 2 operations of the window's decode steps at the chip's peaks over the decode executable's device time under experts_routed."""
from chipbench import families, phases


def read(run):
    return families.of(run["cfg"]).routed_experts_roofline(
        run, phases.DECODE, "decode", "moe_experts_touched")
