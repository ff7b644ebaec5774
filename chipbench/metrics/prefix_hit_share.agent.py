"""Prompt tokens served from the radix prefix cache (behind a state snapshot) over all prompt tokens admitted."""
from chipbench.reduce import prefix_hit_share as read  # noqa: F401
