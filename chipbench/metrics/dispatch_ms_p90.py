"""90th percentile of the master's selection and dispatch of a query, in
ms: the ``master.dispatch`` span on the control plane's scheduler thread,
over queries dispatched in the window. The in-program twin of
``submit_ms_p90``, which also holds the hop from the client's thread."""
from harness import spanread as S
from harness.stats import pct


def read(ctx):
    vals = [S.dur_ns(d["master.dispatch"]) * 1e-6
            for d in S.queries(ctx).values()]
    return pct(vals, 90, 0.0) if vals else None
