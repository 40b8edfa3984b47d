"""prefill_admit's share of its roofline in the traced window, in %:
needed time (real prompt tokens' FLOPs at the bf16 peak, or the bytes each
dispatch must read at HBM bandwidth, the larger) over the device time of
the prefill_admit programs."""
from harness.roofline import prefill_share as read  # noqa: F401
