"""Mean wait in the gateway queue, from gateway.queue_wait_seconds read before and after: a cold context's admission holds the gateway's step, and what arrives meanwhile waits here."""
from chipbench.reduce import queue_wait_ms as read  # noqa: F401
