"""95th percentile of all gaps between output tokens of the requests due in the window (it stands on levels: PERF.md, section 2)."""
from chipbench.reduce import tpot_p95_ms as read  # noqa: F401
