"""Idle device time inside serving.admit, the first token's fetch apart, for each admission."""
from chipbench.phases import gap_admit_ms as read  # noqa: F401
