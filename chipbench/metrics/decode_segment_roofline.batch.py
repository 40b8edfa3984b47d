"""decode_segment's share of its roofline in the traced window, in %, in
a batch cell (harness.roofline.decode_share, as decode_segment_roofline.chat
reads it in the chat cell)."""
from harness.roofline import decode_share as read  # noqa: F401
