"""Required forward+backward FLOPs per token x tokens/s over the peak of the chips used."""
from chipbench.reduce import mfu as read  # noqa: F401
