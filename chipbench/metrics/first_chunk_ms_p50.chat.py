"""Median time from a request's admission into an engine slot to its
first harvested token, in ms: ``engine.first_chunk`` (a prefill plus the
decode segment whose harvest first streams it), over queries dispatched
in the window."""
from harness import spanread as S
from harness.stats import pct


def read(ctx):
    vals = []
    for d in S.queries(ctx).values():
        fc = S.first_chunk(d)
        if fc is not None:
            vals.append((fc.t1_ns - fc.t0_ns) * 1e-6)
    return pct(vals, 50, 0.0) if vals else None
