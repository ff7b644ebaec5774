"""Bytes the decode steps must read over the window time over the peak bandwidth."""
from chipbench.reduce import mbu as read  # noqa: F401
