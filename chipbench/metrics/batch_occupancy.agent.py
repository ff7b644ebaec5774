"""Running sequences per decode step, from the batcher's own occupancy count."""
from chipbench.reduce import occupancy as read  # noqa: F401
