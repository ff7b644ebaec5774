"""Mean duration of a serving.admit span: one admission, from the gate's pass to the first token (a resumed hit one to three chunks, a cold context up to 38)."""
from chipbench.phases import admit_ms as read  # noqa: F401
