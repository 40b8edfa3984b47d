"""90th percentile of the ServingRuntime.submit round trip (the control
plane accepting a query: scheduler-thread hop, selection, dispatch), as
the harness times it around each call, over requests due in the window."""
from harness.stats import pct


def read(ctx):
    vals = [r.submit_s * 1e3 for r in ctx.records if r.sent >= 0]
    return pct(vals, 90, 0.0) if vals else None
