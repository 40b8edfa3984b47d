"""Median time per output token after the first chunk over every request
due in the window (harness.latency.tpot_ms): how fast a reply streams
once it has begun. Failed requests rank last (as the drain cap)."""
from harness.latency import tpot_ms
from harness.stats import pct


def read(ctx):
    vals = tpot_ms(ctx)
    return pct(vals, 50, ctx.drain_cap * 1e3) if vals else None
