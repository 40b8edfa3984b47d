"""The span recorder (``repro.core.spans``): off it is a shared no-op; on,
it nests, bounds its buffer, and the wall-clock served path gives every
query five spans that tile its program-side time to first token."""
import numpy as np
import pytest

from repro.core import spans

PER_QUERY = ("master.dispatch", "worker.queue", "executor.handoff",
             "engine.queue", "engine.first_chunk")
TILE_NS = 5_000_000      # largest gap or overlap between a query's spans


@pytest.fixture(autouse=True)
def recorder_off():
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


def test_off_returns_the_shared_noop_and_records_nothing():
    a, b = spans.span("a"), spans.span("b", qid=3)
    assert a is b
    with a:
        spans.interval("i", 0, 10, qid=1)
        spans.anchor()
    assert not spans.on()
    assert spans.drain() == []


def test_nesting_gives_parents_and_the_buffer_drops_the_oldest():
    spans.enable(capacity=64)
    with spans.span("outer", qid=7):
        with spans.span("inner"):
            pass
        spans.interval("wait", 5, 9, qid=7)
    with spans.span("next"):
        pass
    recs = {r.name: r for r in spans.drain()}
    assert set(recs) == {spans.ANCHOR, "outer", "inner", "wait", "next"}
    assert recs["outer"].parent == -1 and recs["outer"].qid == 7
    assert recs["inner"].parent == recs["outer"].idx
    assert recs["inner"].qid == -1
    assert recs["next"].parent == -1
    assert (recs["wait"].t0_ns, recs["wait"].t1_ns) == (5, 9)
    o, i = recs["outer"], recs["inner"]
    assert o.t0_ns <= i.t0_ns <= i.t1_ns <= o.t1_ns
    assert spans.drain() == []

    spans.enable(capacity=4)
    for k in range(10):
        spans.interval(f"s{k}", k, k + 1)
    spans.disable()
    kept = spans.drain()
    assert [r.name for r in kept] == ["s6", "s7", "s8", "s9"]


@pytest.mark.slow
def test_wall_queries_carry_five_spans_that_tile_their_ttft():
    """Four streamed queries through a tiny wall-clock cluster: each
    finished query has exactly the five per-query spans under its own
    ``qid``, in order, with no gap or overlap above 5 ms between them;
    the step spans nest where they run."""
    from repro.configs.registry import ARCHS
    from repro.core.api import QueryPayload, QuerySpec
    from repro.serving.executor import EngineExecutorConfig
    from repro.serving.runtime import ServingRuntime
    from repro.sim.cluster import make_cluster

    smoke = ARCHS["llama3.2-1b"].reduced()
    c = make_cluster(n_accel=1, archs=[smoke], autoscale=False,
                     backend="real", clock="wall",
                     engine_cfg=EngineExecutorConfig(max_batch=4, max_len=48,
                                                     decode_block=4))
    rt = ServingRuntime(c)
    rng = np.random.default_rng(0)

    def spec(lens):
        return QuerySpec.arch(smoke.name, latency_ms=600_000,
                              payload=QueryPayload.of(
                                  [rng.integers(0, smoke.vocab, size=n)
                                   .astype(np.int32) for n in lens],
                                  max_new_tokens=10))
    try:
        # load the variant, start the stepper and compile every prompt
        # bucket first: the spans then time serving, not set-up
        assert rt.submit(spec([4, 8, 12])).result(timeout=600.0).ok
        spans.enable()
        handles = [rt.submit(spec([int(rng.integers(4, 12))]))
                   for _ in range(4)]
        results = [h.result(timeout=600.0) for h in handles]
        assert all(r.ok for r in results)
    finally:
        assert rt.shutdown(drain=True, timeout=60.0)
        spans.disable()
    recs = spans.drain()
    for h in handles:
        mine = sorted((r for r in recs if r.qid == h.query.qid),
                      key=lambda r: r.t0_ns)
        assert [r.name for r in mine] == list(PER_QUERY), mine
        for a, b in zip(mine, mine[1:]):
            assert a.t0_ns <= a.t1_ns
            assert abs(b.t0_ns - a.t1_ns) <= TILE_NS, (a, b)
    by_idx = {r.idx: r for r in recs}
    names = {r.name for r in recs}
    assert {"stepper.iter", "engine.admit", "engine.sync", "engine.harvest",
            "stepper.pump"} <= names
    for r in recs:
        if r.name in ("engine.admit", "engine.sync", "engine.harvest",
                      "stepper.pump"):
            assert by_idx[r.parent].name == "stepper.iter"
