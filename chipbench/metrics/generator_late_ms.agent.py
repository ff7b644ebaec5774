"""Mean of send time minus due time: how late the load generator ran (it shares its thread with the gateway's step, a cold context's admission included)."""
from chipbench.reduce import generator_late_ms as read  # noqa: F401
