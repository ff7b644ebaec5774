"""Idle device time of the window outside serving.fetch and serving.pick, for each serving.launch."""
from chipbench.phases import gap_host_loop_ms as read  # noqa: F401
