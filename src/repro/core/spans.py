"""Span recorder for the served path: where a query's time goes, layer by
layer, on the clock the engine and the client already use.

One record per span, ``Span(name, qid, parent, t0_ns, t1_ns, thread,
idx)``: ``qid`` is the ``Query.qid`` every span of one query shares (-1
for per-step spans), ``idx`` the span's number in opening order, and
``parent`` the ``idx`` of the span that enclosed it on the same thread
(-1 at the top). Times are ``time.perf_counter_ns()``, the clock of
``Request.arrival/admitted/first_token`` (seconds of the same counter);
never ``loop.now()``, which the virtual clock simulates and ``RealClock``
offsets.

* ``span(name, qid)``: a context manager for work on one thread;
* ``interval(name, t0_ns, t1_ns, qid)``: a wait whose two ends lie in
  different callbacks (enqueue to job start, say), recorded after the
  fact;
* ``enable(capacity)`` / ``disable()`` / ``drain()``: records go into a
  bounded in-memory buffer that drops its oldest records when full, and
  ``drain()`` hands them out. Nothing is written or logged.

Off by default. Off, ``span()`` returns one shared no-op context after a
single check of a module global, and ``interval()`` returns at once. On,
each scoped span also enters ``jax.profiler.TraceAnnotation(name)``, so
inside a profiler window the spans land on the device trace's host plane,
on its clock. ``anchor()`` (also called by ``enable()``) records the span
``spans.anchor``, which a reader of a trace finds in both to map
``perf_counter_ns`` onto the trace's clock.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
from collections import deque
from typing import Deque, List, NamedTuple, Optional


class Span(NamedTuple):
    name: str
    qid: int
    parent: int
    t0_ns: int
    t1_ns: int
    thread: int
    idx: int


ANCHOR = "spans.anchor"
_NOOP = contextlib.nullcontext()
_on = False
_records: Deque[Span] = deque(maxlen=1)
_annotate = None                       # jax.profiler.TraceAnnotation
_seq = itertools.count()
_local = threading.local()


def on() -> bool:
    return _on


def ns(t_s: float) -> int:
    """A ``time.perf_counter()`` reading in the recorder's nanoseconds."""
    return int(round(t_s * 1e9))


class _Scoped:
    __slots__ = ("name", "qid", "parent", "idx", "t0", "ann")

    def __init__(self, name: str, qid: int):
        self.name, self.qid = name, qid

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.parent = stack[-1] if stack else -1
        self.idx = next(_seq)
        stack.append(self.idx)
        self.ann = _annotate(self.name) if _annotate is not None else _NOOP
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        _local.stack.pop()
        _records.append(Span(self.name, self.qid, self.parent, self.t0, t1,
                             threading.get_ident(), self.idx))
        return False


def span(name: str, qid: int = -1):
    """Context manager timing the enclosed work as one span."""
    if not _on:
        return _NOOP
    return _Scoped(name, qid)


def interval(name: str, t0_ns: int, t1_ns: Optional[int] = None,
             qid: int = -1) -> None:
    """Record a span from ``t0_ns`` to ``t1_ns`` (now when None)."""
    if not _on:
        return
    if t1_ns is None:
        t1_ns = time.perf_counter_ns()
    _records.append(Span(name, qid, -1, int(t0_ns), int(t1_ns),
                         threading.get_ident(), next(_seq)))


def anchor() -> None:
    """Record ``spans.anchor`` (and, inside a profiler window, put it on
    the trace) so that the two clocks can be matched."""
    with span(ANCHOR):
        pass


def enable(capacity: int = 1 << 16) -> None:
    """Turn the recorder on with room for ``capacity`` records; records
    not yet drained are dropped."""
    global _on, _records, _annotate
    try:
        from jax.profiler import TraceAnnotation
        _annotate = TraceAnnotation
    except ImportError:          # the control plane runs without JAX
        _annotate = None
    _records = deque(maxlen=int(capacity))
    _on = True
    anchor()


def disable() -> None:
    """Turn the recorder off; records not yet drained stay for ``drain``."""
    global _on
    _on = False


def drain() -> List[Span]:
    """Hand out (and forget) every record so far, in opening order."""
    out = []
    while True:
        try:
            out.append(_records.popleft())
        except IndexError:
            return sorted(out, key=lambda s: s.idx)
