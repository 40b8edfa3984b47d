"""Percentiles over every request due in the window, failures included.

A failed request (not finished within the drain cap, or finished with an
error) ranks last. Linear interpolation between order statistics, as
``numpy.percentile`` does by default; where the percentile reaches a
failed request the value is the drain cap.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence


def pct(values: Sequence[Optional[float]], q: float, cap: float) -> float:
    """``values``: one entry per request, ``None`` for a failed one."""
    if not values:
        return float("nan")
    xs = sorted(math.inf if v is None else float(v) for v in values)
    pos = (len(xs) - 1) * q / 100.0
    i = int(math.floor(pos))
    j = min(i + 1, len(xs) - 1)
    if math.isinf(xs[j] if pos > i else xs[i]):
        return float(cap)
    return xs[i] + (xs[j] - xs[i]) * (pos - i)

