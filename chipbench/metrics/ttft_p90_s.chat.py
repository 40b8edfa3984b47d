"""90th percentile of time to first token over every request due in the
window (harness.latency.ttft). Failed requests rank last. Read in the
traced run (see ttft_p50_s.chat)."""
from harness.latency import ttft
from harness.stats import pct


def read(ctx):
    return pct(ttft(ctx), 90, ctx.drain_cap) if ctx.records else None
