"""The program's spans as the benchmark reads them: a scoped span lands in
the profiler trace's host plane and ``spanread.align`` puts the recorder's
clock on the trace's; each metric reader on hand-built records; and a
tiny traced run with the recorder on reports every one of them."""
import time

import pytest

import chipbench_tiny  # noqa: F401
from harness import spanread, spec, tracing

from repro.core import spans
from repro.core.spans import Span

CELL = "phi3-int8-chat"
READERS = ("dispatch_ms_p90", "worker_queue_s_p50.chat",
           "ttft_queue_share.chat", "first_chunk_ms_p50.chat",
           "stepper_host_ms_per_segment.chat")


@pytest.fixture
def recorder():
    spans.enable()
    yield
    spans.disable()
    spans.drain()


def test_scoped_span_lands_in_the_trace_and_aligns(tmp_path, recorder):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans.anchor()
        with spans.span("probe.work"):
            time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    recs = spans.drain()
    (mine,) = [r for r in recs if r.name == "probe.work"]
    tr = tracing.load(str(tmp_path))
    (ev,) = [e for e in tr.host if e[0] == "probe.work"]
    assert abs((ev[2] - ev[1]) - (mine.t1_ns - mine.t0_ns) * 1e-9) < 50e-6
    off = spanread.align(tr, recs)
    assert off is not None
    assert abs(mine.t0_ns * 1e-9 + off - ev[1]) < 100e-6
    assert spanread.matched(tr, recs, off) >= 2     # the anchor and the work


class _Ctx:
    """What a metric reader sees of a run: the window, the engine's
    counters over it, and (from a harness that records them) the spans."""

    def __init__(self, recs, segments=2):
        self.spans = recs
        self.segments = segments

    @staticmethod
    def in_window(t):
        return 10.0 <= t < 20.0

    def delta(self, key, traced=False):
        return self.segments if key == "decode_dispatches" else 0


def _s(name, t0, t1, qid=-1, idx=0, parent=-1):
    return Span(name, qid, parent, int(t0 * 1e9), int(t1 * 1e9), 1, idx)


def _records():
    q = []
    for qid, (t, disp, wq, fc) in {1: (11.0, 0.0005, 0.9996, 0.5),
                                   2: (15.0, 0.001, 0.0, 0.4),
                                   3: (25.0, 0.005, 3.0, 2.0)}.items():
        a = t + disp
        b = (t + 0.0004 if qid == 1 else a) + wq
        q += [_s("master.dispatch", t, a, qid),
              _s("worker.queue", b - wq, b, qid),
              _s("executor.handoff", b, b + 0.01, qid),
              _s("engine.queue", b + 0.01, b + 0.02, qid),
              _s("engine.first_chunk", b + 0.02, b + 0.02 + fc, qid)]
    steps = [_s("stepper.iter", 10.5, 10.6, idx=100),
             _s("engine.admit", 10.5, 10.51, idx=101, parent=100),
             _s("engine.sync", 10.52, 10.58, idx=102, parent=100),
             _s("stepper.iter", 16.0, 16.05, idx=200),
             _s("engine.sync", 16.01, 16.04, idx=201, parent=200),
             _s("stepper.iter", 30.0, 31.0, idx=300),      # after the window
             _s("engine.sync", 30.1, 30.2, idx=301, parent=300)]
    return q + steps


EXPECTED = {
    "dispatch_ms_p90": 0.5 + 0.9 * 0.5,
    "worker_queue_s_p50.chat": 0.9996 / 2,
    # queued 0.9996 s of (1.52 + 0.421) s of time to first token
    "ttft_queue_share.chat": 100 * 0.9996 / (1.52 + 0.421),
    "first_chunk_ms_p50.chat": 450.0,
    # (100 - 60) + (50 - 30) ms of host time over 2 segments
    "stepper_host_ms_per_segment.chat": 30.0,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_on_hand_built_spans(name):
    read = spec.reader(name)
    assert read(_Ctx(_records())) == pytest.approx(EXPECTED[name], rel=1e-6)
    # a harness without Ctx.spans (or a run that recorded none): no value
    assert read(_Ctx(None)) is None
    assert read(_Ctx([])) is None


def test_tiling_measures_gaps_and_overlaps():
    d = spanread.by_query(_records())[1]
    got = spanread.tiling(d)
    assert got == pytest.approx([-100_000, 0, 0, 0], abs=2)
    del d["executor.handoff"]
    assert spanread.tiling(d) is None


def test_idle_seconds_go_to_the_innermost_program_span():
    from harness.tracing import Device
    d = Device("d", [], [("op", 0, 2), ("op", 5, 6), ("op", 9, 10)])
    host = [("stepper.iter", 1, 7), ("engine.sync", 1.5, 2.5),
            ("engine.harvest", 2.5, 3), ("np.asarray", 1.9, 2.4),
            ("stepper.wait", 7, 8.5)]
    got = dict(spanread.idle_by_span(
        d, host, 0, 10, {"stepper.iter", "engine.sync", "engine.harvest",
                         "stepper.wait"}))
    assert got == pytest.approx({"engine.sync": 0.5, "engine.harvest": 0.5,
                                 "stepper.iter": 3.0, "stepper.wait": 1.5,
                                 "no span": 0.5})


def test_tiny_traced_run_with_spans_reports_them():
    import spanprobe
    from chipbench_tiny import CPU_PEAKS, tiny
    bench, cfg, mix, c = tiny(CELL)
    out = spanprobe.run(bench, CELL, 17, 1.5, True, time.perf_counter(),
                        require_chip=False, cfg=cfg, mix=mix, cell=c,
                        peaks=CPU_PEAKS, cache=False)
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert set(READERS) <= set(m), m
    assert all(m[k]["value"] is not None for k in READERS)
    assert "tpot_p50_ms" in m and "slot_busy_frac.chat" in m
    s = out["spans"]
    assert s["queries"] > 0 and s["tiled"] == s["queries"]
    assert s["tiling_residual_ms_max"] <= 5.0
    assert s["align_matched"] > 0
    assert list(out)[-1] == "checks"
