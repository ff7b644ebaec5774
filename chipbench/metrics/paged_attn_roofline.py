"""K and V bytes the decode steps had to read at the peak bandwidth, over the decode executable's device time under paged_attention."""
from chipbench.phases import paged_attn_roofline as read  # noqa: F401
