"""Mean of send time minus due time: how late the load generator ran."""
from chipbench.reduce import generator_late_ms as read  # noqa: F401
