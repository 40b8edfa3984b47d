"""Client-side latencies of the requests due in the window, as the
harness's clock saw them at the client API's callbacks. A failed request
(not finished within the drain cap, or finished with an error) has no
latency: ``None``, which the statistics rank last or count at the cap."""
from __future__ import annotations

from typing import List, Optional


def ttft(ctx) -> List[Optional[float]]:
    """Seconds from each request's scheduled send time to the callback
    that delivered its first tokens."""
    return [(r.first - r.due) if (r.ok and r.first is not None) else None
            for r in ctx.records]


def tpot_ms(ctx) -> List[Optional[float]]:
    """Milliseconds per output token after the first chunk: (last chunk -
    first chunk) / tokens after the first chunk. A request whose tokens
    all came in one chunk has no such time and is left out."""
    vals: List[Optional[float]] = []
    for r in ctx.records:
        if not r.ok:
            vals.append(None)
            continue
        after = sum(n for _, n, _ in r.chunks[1:])
        if after > 0:
            vals.append((r.chunks[-1][0] - r.chunks[0][0]) / after * 1e3)
    return vals
