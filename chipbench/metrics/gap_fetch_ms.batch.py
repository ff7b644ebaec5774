"""Idle device time under serving.fetch and serving.pick, for each serving.launch."""
from chipbench.phases import gap_fetch_ms as read  # noqa: F401
