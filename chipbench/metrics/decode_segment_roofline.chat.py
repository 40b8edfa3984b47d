"""decode_segment's share of its roofline in the traced window, in %:
needed bytes per step (weights as stored, K and V of live positions only)
at HBM bandwidth over the decode programs' device time. Chat cells."""
from harness.roofline import decode_share as read  # noqa: F401
