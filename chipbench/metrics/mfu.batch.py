"""Whole-step model FLOPs utilisation over the window, in %, in a batch
cell (harness.roofline.step_mfu, as mfu.chat reads it in the chat cell)."""
from harness.roofline import step_mfu as read  # noqa: F401
