"""95th percentile of all gaps between output tokens of the requests due in the window: the decode step's gap, where the mean also holds the gaps under admissions."""
from chipbench.reduce import tpot_p95_ms as read  # noqa: F401
