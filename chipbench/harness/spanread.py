"""Read the program's span records (``repro.core.spans``) that a traced
run hands the metric readers as ``ctx.spans``, and put them on the clock
of a device trace.

A record has the fields ``name, qid, parent, t0_ns, t1_ns, thread, idx``,
in nanoseconds of ``time.perf_counter``, the clock of the harness's own
timestamps (``ctx.in_window`` takes its seconds). A run with no
``ctx.spans`` (a harness that does not turn the recorder on, or a program
that has none) gives every reader None.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

# the spans of one query, in the order they tile its program-side time to
# first token: selection and dispatch, the worker's queue, the hop to the
# stepper thread, the engine's queue, admission to the first harvested
# token
PER_QUERY = ("master.dispatch", "worker.queue", "executor.handoff",
             "engine.queue", "engine.first_chunk")
ANCHOR = "spans.anchor"


def records(ctx) -> Optional[List[Any]]:
    return getattr(ctx, "spans", None) or None


def dur_ns(recs) -> int:
    return sum(r.t1_ns - r.t0_ns for r in recs)


def by_query(recs) -> Dict[int, Dict[str, List[Any]]]:
    """Per-query spans by ``qid`` and name, for every query with a
    ``master.dispatch``."""
    by: Dict[int, Dict[str, List[Any]]] = defaultdict(
        lambda: defaultdict(list))
    for r in recs:
        if r.qid >= 0 and r.name in PER_QUERY:
            by[r.qid][r.name].append(r)
    return {q: d for q, d in by.items() if d["master.dispatch"]}


def queries(ctx) -> Dict[int, Dict[str, List[Any]]]:
    """``by_query`` of the queries whose ``master.dispatch`` began inside
    the window."""
    return {q: d for q, d in by_query(records(ctx) or ()).items()
            if ctx.in_window(min(r.t0_ns for r in d["master.dispatch"])
                             * 1e-9)}


def first_chunk(d: Dict[str, List[Any]]) -> Optional[Any]:
    """The ``engine.first_chunk`` that delivered the query's first token."""
    fc = d.get("engine.first_chunk")
    return min(fc, key=lambda r: r.t1_ns) if fc else None


def ttft_ns(d: Dict[str, List[Any]]) -> Optional[int]:
    """Program-side time to first token: dispatch start to first token."""
    fc = first_chunk(d)
    if fc is None:
        return None
    return fc.t1_ns - min(r.t0_ns for r in d["master.dispatch"])


def tiling(d: Dict[str, List[Any]]) -> Optional[List[int]]:
    """Gaps (positive) and overlaps (negative) in ns between each pair of
    consecutive per-query spans of one query (first of each name), or
    None where one is missing."""
    firsts = []
    for name in PER_QUERY:
        rs = d.get(name)
        if not rs:
            return None
        firsts.append(min(rs, key=lambda r: r.t0_ns))
    return [b.t0_ns - a.t1_ns for a, b in zip(firsts, firsts[1:])]


def matched(trace, recs, offset: float, tol_s: float = 2e-4) -> int:
    """How many of the trace's host events meet a record of the same name
    that starts within ``tol_s`` of them once shifted by ``offset``."""
    starts: Dict[str, List[float]] = defaultdict(list)
    for r in recs:
        starts[r.name].append(r.t0_ns * 1e-9 + offset)
    for v in starts.values():
        v.sort()
    hit = 0
    for n, s, _ in trace.host:
        xs = starts.get(n)
        if xs:
            i = bisect.bisect_left(xs, s - tol_s)
            hit += i < len(xs) and xs[i] <= s + tol_s
    return hit


def align(trace, recs) -> Optional[float]:
    """The offset (s) that maps a record's ``perf_counter_ns`` onto the
    trace's clock: trace time = t_ns * 1e-9 + offset. Each pair of an
    anchor in the trace's host plane and a recorded anchor proposes one;
    the one under which most of the trace's program spans meet their
    records wins. None without an anchor in both."""
    tr_anchors = [s for n, s, _ in trace.host if n == ANCHOR]
    rec_anchors = [r.t0_ns * 1e-9 for r in recs if r.name == ANCHOR]
    if not tr_anchors or not rec_anchors:
        return None
    offsets = [a - b for a in tr_anchors for b in rec_anchors]
    return max(offsets, key=lambda off: matched(trace, recs, off))


def idle_by_span(dev, host, lo: float, hi: float,
                 names) -> List[Tuple[str, float]]:
    """The device's idle seconds in [lo, hi) by the innermost program span
    (a host event whose name is in ``names``) covering each idle instant,
    longest first; "no span" where none does. Unlike
    ``tracing.label_gaps``, which takes the event overlapping a whole gap
    most, this splits a gap between the spans that cover its parts."""
    from harness import tracing
    prog = sorted((s, e, n) for n, s, e in host if n in names)
    longest = max((e - s for s, e, _ in prog), default=0.0)
    starts = [s for s, _, _ in prog]
    acc: Dict[str, float] = defaultdict(float)
    for g0, g1 in tracing.idle_gaps(dev, lo, hi):
        i = bisect.bisect_left(starts, g0 - longest)
        cover = []
        while i < len(prog) and prog[i][0] < g1:
            s, e, n = prog[i]
            if e > g0:
                cover.append((max(s, g0), min(e, g1), n, e - s))
            i += 1
        cuts = sorted({g0, g1} | {t for c in cover for t in c[:2]})
        for a, b in zip(cuts, cuts[1:]):
            inner = [c for c in cover if c[0] <= a and b <= c[1]]
            name = min(inner, key=lambda c: c[3])[2] if inner else "no span"
            acc[name] += b - a
    return sorted(acc.items(), key=lambda kv: -kv[1])
