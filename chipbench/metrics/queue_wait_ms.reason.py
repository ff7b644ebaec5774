"""Mean wait in the gateway queue, from gateway.queue_wait_seconds read before and after."""
from chipbench.reduce import queue_wait_ms as read  # noqa: F401
