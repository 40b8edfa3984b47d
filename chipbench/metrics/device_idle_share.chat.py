"""Share of the traced window in which no operation ran on the device,
in %: 1 - union of op intervals / traced span."""
from harness import tracing


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    span = ctx.win.trace_hi - ctx.win.trace_lo
    busy = tracing.busy_s(ctx.trace.devices[0])
    if span <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / span)
