"""Share of its roofline that the flash forward kernel reached, from its device events."""
from chipbench import costs
from chipbench.reduce import kernel_roofline


def read(run):
    return kernel_roofline(run, ("flash_fwd",), costs.flash_fwd_cost)
