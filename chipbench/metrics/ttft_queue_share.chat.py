"""Share of the program-side time to first token spent in the worker's
queue, in %: the sum of ``worker.queue`` over the sum of (end of
``engine.first_chunk`` - start of ``master.dispatch``), over queries
dispatched in the window that streamed a first token."""
from harness import spanread as S


def read(ctx):
    queued = total = 0
    for d in S.queries(ctx).values():
        t = S.ttft_ns(d)
        if t is not None:
            queued += S.dur_ns(d.get("worker.queue", ()))
            total += t
    return 100.0 * queued / total if total > 0 else None
