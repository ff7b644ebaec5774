"""Mean host time of a gateway step of the window; tokens are on the host when it returns."""
from chipbench.reduce import mean_step_ms as read  # noqa: F401
