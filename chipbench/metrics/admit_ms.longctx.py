"""Mean duration of a serving.admit span: one admission, from the gate's pass to the first token."""
from chipbench.phases import admit_ms as read  # noqa: F401
