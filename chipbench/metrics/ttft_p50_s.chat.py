"""Median time to first token over every request due in the window, from
its scheduled send time (harness.latency.ttft). Failed requests rank
last. Read in the traced run: its spread between runs is too wide for an
end-to-end bound (PERF.md)."""
from harness.latency import ttft
from harness.stats import pct


def read(ctx):
    return pct(ttft(ctx), 50, ctx.drain_cap) if ctx.records else None
