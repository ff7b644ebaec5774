"""The convolution operators' 2 x 16.78M operations a prefilled row and layer at the peak rate over the prefill-chunk executable's device time under short_conv (state reads and snapshot writes included)."""
from chipbench import families, phases


def read(run):
    if not run.get("peaks") or not run.get("miss_tokens"):
        return None
    family = families.of(run["cfg"])
    flops = family.short_conv_prefill_flops(run["cfg"], run["miss_tokens"])
    return family.share_of_least(
        run, phases.PREFILL_CHUNK, ("short_conv",),
        flops / run["peaks"]["bf16_flops"], "short_conv_prefill", "compute")
