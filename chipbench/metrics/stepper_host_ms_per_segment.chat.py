"""Host time the engine stepper spends per decode segment, in ms: the sum
over ``stepper.iter`` spans begun in the window of their length less that
of their ``engine.sync`` (the wait for the device), over the decode
segments dispatched in the window (the engine's ``decode_dispatches``)."""
from harness import spanread as S


def read(ctx):
    recs = S.records(ctx)
    segs = ctx.delta("decode_dispatches")
    if recs is None or segs <= 0:
        return None
    iters = {r.idx: r for r in recs if r.name == "stepper.iter"
             and ctx.in_window(r.t0_ns * 1e-9)}
    if not iters:
        return None
    sync = S.dur_ns(r for r in recs
                    if r.name == "engine.sync" and r.parent in iters)
    return (S.dur_ns(iters.values()) - sync) * 1e-6 / segs
