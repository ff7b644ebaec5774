"""Mean device time of one execution of the decode executable in the window, from the trace's module line."""
from chipbench import phases


def read(run):
    return phases.device_ms(run, phases.DECODE)
