"""The convolution operators' weights (once a decode step) and every running slot's state read and written, at the peak bandwidth, over the decode executable's device time a step under short_conv AND under no scope: XLA fuses the operator's out-projection with the unscoped norm behind it, so the time under the scope alone leaves out part of the work (PERF.md section 5: 0.23 ms a step under the scope where the weights alone need 0.29); with the executable's unscoped time counted the share is a lower bound."""
from chipbench import families, phases


def read(run):
    if not run.get("peaks") or not run.get("decode_steps"):
        return None
    family = families.of(run["cfg"])
    nbytes = family.short_conv_decode_bytes(
        run["cfg"], run["decode_steps"], run["occupancy_sum"])
    return family.share_of_least(
        run, phases.DECODE, ("short_conv", "no_scope"),
        nbytes / run["peaks"]["hbm_bytes_per_s"], "short_conv_decode")
