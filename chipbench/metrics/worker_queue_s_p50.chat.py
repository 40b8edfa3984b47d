"""Median wait of a query in its worker's queue, in s: ``worker.queue``,
from ``Worker.enqueue`` to the start of the job that carries it (a worker
runs one job at a time on an accelerator), over queries dispatched in the
window."""
from harness import spanread as S
from harness.stats import pct


def read(ctx):
    vals = [S.dur_ns(d["worker.queue"]) * 1e-9
            for d in S.queries(ctx).values() if d.get("worker.queue")]
    return pct(vals, 50, 0.0) if vals else None
