"""The reduction from a profiler trace to busy time, per-program time and
labelled idle gaps: synthetic events for the edge cases, and a slice of a
trace recorded on a TPU v5e by ``chipbench/run.py --trace 1``."""
import os

import pytest

import chipbench_tiny  # noqa: F401
from harness import tracing
from harness.roofline import DECODE, PREFILL
from harness.tracing import Device, Trace

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "phi3_int8_chat_trace.json.gz")


def test_union_merges_overlaps_touching_and_nested():
    got = tracing.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 12),
                         (10.5, 11), (7, 7)])
    assert got == [(0, 4), (5, 6), (10, 12)]


def test_busy_counts_overlapping_ops_once():
    d = Device("d", [("jit_prefill_admit", 0.0, 1.0)],
               [("a", 0.0, 0.5), ("b", 0.25, 0.75), ("c", 0.9, 1.0)])
    assert tracing.busy_s(d) == pytest.approx(0.85)
    # a trace without an op line falls back to whole programs
    assert tracing.busy_s(Device("d", [("m", 0, 2), ("m", 1, 3)], [])) == 3


def test_module_time_by_name():
    d = Device("d", [("jit_prefill_admit(1)", 0, 1),
                     ("jit__lambda_(2)", 1, 3),
                     ("jit_decode_segment(3)", 3, 4),
                     ("jit_convert_element_type", 4, 4.5)], [])
    assert tracing.module_time(d, PREFILL) == (1, 1)
    assert tracing.module_time(d, DECODE) == (3, 2)


def test_idle_gaps_at_edges_and_between():
    d = Device("d", [], [("a", 1, 2), ("b", 1.5, 3), ("c", 4, 5)])
    assert tracing.idle_gaps(d, 0, 6) == [(0, 1), (3, 4), (5, 6)]
    assert tracing.idle_gaps(d, 1, 5) == [(3, 4)]
    assert tracing.idle_gaps(Device("d", [], []), 0, 2) == [(0, 2)]


def test_gap_labels_take_the_host_event_that_overlaps_most():
    host = [("PjitFunction(decode)", 0.0, 10.0),     # spans everything
            ("submit", 2.2, 2.9), ("harvest", 2.0, 2.3),
            ("idle_poll", 6.0, 6.1)]
    gaps = [(2.0, 3.0), (5.0, 5.5), (6.0, 6.1), (11.0, 12.0)]
    assert tracing.label_gaps(gaps, host) == [
        "PjitFunction(decode)", "PjitFunction(decode)", "idle_poll",
        "host idle"]
    d = Device("d", [], [("op", 0, 2), ("op", 3, 5), ("op", 5.5, 6),
                         ("op", 6.1, 11)])
    got = dict(tracing.gaps_by_label(d, host, 0, 12))
    assert got["PjitFunction(decode)"] == pytest.approx(1.5)
    assert got["idle_poll"] == pytest.approx(0.1)
    assert got["host idle"] == pytest.approx(1.0)


def test_clip_and_round_trip(tmp_path):
    t = Trace([Device("d", [("m", 0, 4)], [("o", 1, 2), ("o", 3, 6)])],
              [("h", -1, 1)], -1, 6)
    c = t.clip(0.5, 3.5)
    assert c.devices[0].ops == [("o", 1, 2), ("o", 3, 3.5)]
    assert c.host == [("h", 0.5, 1)]
    p = str(tmp_path / "t.json.gz")
    c.save(p)
    assert Trace.read(p) == c


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded chip trace in the tree")
def test_recorded_chip_trace():
    """A quarter second of the traced window of phi3-int8-chat on one
    TPU v5e: the decode program runs there, every op lies inside some
    program, and busy plus idle time is the traced span."""
    t = Trace.read(RECORDED)
    assert len(t.devices) == 1
    d = t.devices[0]
    dec, n = tracing.module_time(d, DECODE)
    assert n >= 1 and dec > 0
    busy = tracing.busy_s(d)
    idle = sum(e - s for s, e in tracing.idle_gaps(d, t.lo, t.hi))
    assert busy + idle == pytest.approx(t.hi - t.lo, rel=1e-6)
    mods = tracing.union((s, e) for _, s, e in d.modules)
    for _, s, e in d.ops:
        assert any(ms - 1e-6 <= s and e <= me + 1e-6 for ms, me in mods)
    top = tracing.top_ops(d, 10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    assert tracing.gaps_by_label(d, t.host, t.lo, t.hi)
