"""Mean time of a train step over the window: host clock, each step ends in block_until_ready."""
from chipbench.reduce import mean_step_ms as read  # noqa: F401
