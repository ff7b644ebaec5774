"""Share of its roofline that the flash backward (dq and dkv kernels together) reached."""
from chipbench import costs
from chipbench.reduce import kernel_roofline


def read(run):
    return kernel_roofline(run, ("flash_bwd_dq", "flash_bwd_dkv"),
                           costs.flash_bwd_cost)
