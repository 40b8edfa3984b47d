"""Whole-step model FLOPs utilisation over the window, in %: prefill and
decode FLOPs of what was delivered, over window x bf16 peak."""
from harness.roofline import step_mfu as read  # noqa: F401
