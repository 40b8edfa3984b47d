"""90th percentile of time per output token after the first chunk, per
request due in the window (harness.latency.tpot_ms). Failed requests rank
last (as the drain cap). Read in the traced run (see ttft_p50_s.chat)."""
from harness.latency import tpot_ms
from harness.stats import pct


def read(ctx):
    vals = tpot_ms(ctx)
    return pct(vals, 90, ctx.drain_cap * 1e3) if vals else None
