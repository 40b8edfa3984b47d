"""Deterministic fault injection + health-aware routing (ISSUE 10).

* ``FaultInjector`` replays bit-for-bit: schedules fire at exact consult
  counts, rated sites draw from a seeded stream that other sites'
  consults never perturb;
* injected worker crashes / hangs ride the monitor daemon and resolve
  through the master's existing retry machinery — no query stranded;
* the per-worker circuit breaker trips on consecutive dispatch
  failures, engine-pressure bursts, or heartbeat staleness, routes
  queries around the open worker, half-opens after the cooldown, and
  closes (or re-opens) on the probe's outcome;
* under the wall-clock runtime, a stepper-thread death fails in-flight
  jobs into the retry path and the restarted thread finishes them, and
  ``deadline_enforce`` surfaces engine-level cancellation as the
  ``QueryResult.timed_out`` verdict on a resolved handle.
"""
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.api import QueryPayload, QuerySpec
from repro.core.master import MasterConfig
from repro.serving.executor import EngineExecutorConfig
from repro.serving.faults import FaultInjector, InjectedFault
from repro.serving.runtime import ServingRuntime
from repro.sim.cluster import make_cluster

LLAMA = ARCHS["llama3.2-1b"]
SMOKE = LLAMA.reduced()   # what the real-backend clusters serve
slow = pytest.mark.slow


def _done(q):
    return q.finish >= 0 and not q.failed


# ---------------------------------------------------------------------
# the injector itself (fast, pure)

def test_injector_schedule_is_exact_and_seeded_rates_replay():
    mk = lambda: FaultInjector(seed=42, schedule={"s": [2, 5]},  # noqa: E731
                               rates={"r": 0.3})
    a, b = mk(), mk()
    pat_a = [(a.fire("s"), a.fire("r")) for _ in range(30)]
    pat_b = [(b.fire("s"), b.fire("r")) for _ in range(30)]
    assert pat_a == pat_b, "same seed+schedule must replay bit-for-bit"
    assert [i + 1 for i, (s, _) in enumerate(pat_a) if s] == [2, 5]
    hits = [r for _, r in pat_a]
    assert any(hits) and not all(hits)
    c = a.counts()
    assert c["s"] == {"calls": 30, "fired": 2}
    assert c["r"]["calls"] == 30
    # sites not mentioned never fire
    assert not any(a.fire("quiet") for _ in range(50))
    # schedule-only consults don't consume the rated stream: "r" fires
    # identically with and without the interleaved "s" consults
    d = FaultInjector(seed=42, rates={"r": 0.3})
    assert [d.fire("r") for _ in range(30)] == hits


def test_injector_check_raises_typed_fault():
    f = FaultInjector(schedule={"x": [1]})
    with pytest.raises(InjectedFault, match="site 'x'") as ei:
        f.check("x")
    assert ei.value.site == "x"
    f.check("x")                     # consult 2: silent
    assert f.counts()["x"] == {"calls": 2, "fired": 1}


# ---------------------------------------------------------------------
# worker-level sites through the sim cluster (fast, virtual clock)

@pytest.mark.parametrize("site", ["worker_crash", "worker_hang"])
def test_injected_worker_failure_retries_elsewhere(site):
    """A monitor-daemon crash or hang on one of two workers fails (or
    strands, until the heartbeat sweep notices) its queries; every one
    re-dispatches to the survivor and completes."""
    inj = FaultInjector(schedule={site: [5]})
    cfg = MasterConfig()
    cfg.worker.faults = inj
    c = make_cluster(n_accel=2, archs=[LLAMA], autoscale=False, cfg=cfg)
    c.api.online_query(mod_arch=LLAMA.name, latency_ms=10_000)
    c.run_until(6.0)                 # < 5 monitor consults: nobody dead yet
    qs = [c.api.online_query(mod_arch=LLAMA.name, latency_ms=60_000)
          for _ in range(32)]
    c.run_until(240.0)
    assert inj.fired.get(site) == 1
    dead = [n for n, st in c.store.workers.items() if not st.alive]
    assert len(dead) == 1, "exactly one worker should have been killed"
    done = [q for q in qs if _done(q)]
    assert len(done) == len(qs), \
        f"{len(done)}/{len(qs)} completed after injected {site}"


# ---------------------------------------------------------------------
# circuit breaker (fast, master-side; sim backend is the real code path)

def _warm_cluster(cfg):
    c = make_cluster(n_accel=2, archs=[LLAMA], autoscale=False, cfg=cfg)
    c.api.online_query(mod_arch=LLAMA.name, latency_ms=10_000)
    c.run_until(30.0)
    return c


def test_breaker_trips_at_threshold_and_routes_around():
    cfg = MasterConfig(breaker_enabled=True, breaker_fail_threshold=3,
                       breaker_cooldown=1e9)
    c = _warm_cluster(cfg)
    m = c.master
    victim, other = sorted(m.workers)[:2]
    for _ in range(2):
        m._breaker_failure(victim)
        assert not c.store.workers[victim].blacklisted
    m._breaker_failure(victim)       # third consecutive failure trips
    assert c.store.workers[victim].blacklisted
    assert m.breaker_events[-1][1:] == (victim, "open")
    assert m._breakers[victim].trips == 1
    m._breaker_failure(victim)       # open swallows further signals
    assert m._breakers[victim].trips == 1
    # a success while closed resets the consecutive count
    m._breaker_failure(other)
    m._breaker_failure(other)
    m._breaker_success(other)
    assert m._breakers[other].failures == 0
    assert not c.store.workers[other].blacklisted
    # every query routes around the open worker and still completes
    t0 = c.loop.now()
    qs = [c.api.online_query(mod_arch=LLAMA.name, latency_ms=60_000)
          for _ in range(8)]
    c.run_until(t0 + 120.0)
    assert all(_done(q) for q in qs)
    assert all(q.worker != victim for q in qs), \
        "query dispatched to a breaker-open worker"


def test_breaker_halfopen_probe_cycle():
    """open -> (cooldown) -> half-open -> probe failure -> open again ->
    (cooldown) -> half-open -> probe success -> closed, with the
    blacklist flag tracking exactly the open state."""
    cfg = MasterConfig(breaker_enabled=True, breaker_fail_threshold=1,
                       breaker_cooldown=5.0)
    c = _warm_cluster(cfg)
    m = c.master
    victim = sorted(m.workers)[0]
    m._breaker_failure(victim)
    assert m._breakers[victim].state == "open"
    assert c.store.workers[victim].blacklisted
    c.run_until(c.loop.now() + 30.0)     # sweep rides the failure sweep
    assert m._breakers[victim].state == "half-open"
    assert not c.store.workers[victim].blacklisted
    m._breaker_failure(victim)           # probe failed: straight back open
    assert m._breakers[victim].state == "open"
    assert c.store.workers[victim].blacklisted
    assert m._breakers[victim].trips == 2
    c.run_until(c.loop.now() + 30.0)
    assert m._breakers[victim].state == "half-open"
    m._breaker_success(victim)           # probe succeeded: closed
    assert m._breakers[victim].state == "closed"
    assert not c.store.workers[victim].blacklisted
    assert [s for _, n, s in m.breaker_events if n == victim] == \
        ["open", "half-open", "open", "half-open", "closed"]


def test_breaker_pressure_and_staleness_signals():
    """The sweep folds data-plane pressure deltas (the executor's
    monotone preemption+stall total since the last sweep — NOT the
    occupancy log, which is a bounded deque whose positional indexing
    drifts once it wraps) and sub-timeout heartbeat staleness into
    failure signals; already-folded pressure never re-counts."""
    cfg = MasterConfig(breaker_enabled=True, breaker_fail_threshold=2,
                       breaker_pressure_threshold=8, breaker_cooldown=1e9)
    c = _warm_cluster(cfg)
    m = c.master
    victim = sorted(m.workers)[0]
    w = m.workers[victim]
    w.executor.pressure_events_total = 9.0   # delta 9 >= 8
    m._breaker_sweep(c.loop.now())
    assert m._breakers[victim].failures == 1
    m._breaker_sweep(c.loop.now())       # no new pressure: no new signal
    assert m._breakers[victim].failures == 1
    w.executor.pressure_events_total += 8.0
    m._breaker_sweep(c.loop.now())       # second signal trips (threshold 2)
    assert m._breakers[victim].state == "open"
    # staleness past half the heartbeat timeout (worker lagging, not yet
    # dead) is a signal too
    other = sorted(m.workers)[1]
    c.store.workers[other].heartbeat = \
        c.loop.now() - 0.6 * cfg.heartbeat_timeout
    m._breaker_sweep(c.loop.now())
    assert m._breakers[other].failures == 1


# ---------------------------------------------------------------------
# wall-clock chaos: stepper death and deadline enforcement (slow)

def _spec(rng, max_new=10, latency_ms=600_000):
    vocab = SMOKE.vocab
    prompt = rng.integers(0, vocab,
                          size=int(rng.integers(4, 10))).astype(np.int32)
    return QuerySpec.arch(SMOKE.name, latency_ms=latency_ms,
                          payload=QueryPayload.of([prompt],
                                                  max_new_tokens=max_new))


def _wall_cluster(ecfg):
    return make_cluster(n_accel=1, archs=[SMOKE], autoscale=False,
                        backend="real", clock="wall", engine_cfg=ecfg)


@slow
def test_stepper_death_fails_inflight_into_retry():
    """An injected stepper-thread death mid-decode must not wedge the
    in-flight job: the executor fails it through on_done (master retry),
    restarts the thread, and the retried query completes."""
    faults = FaultInjector(schedule={"stepper": [2]})
    c = _wall_cluster(EngineExecutorConfig(max_batch=4, max_len=48,
                                           decode_block=4, faults=faults))
    rt = ServingRuntime(c)
    try:
        h = rt.submit(_spec(np.random.default_rng(0)))
        res = h.result(timeout=600.0)
        assert res.ok, (res.failed, res.variant)
        assert res.attempts > 1, "query never went around the retry loop"
        assert faults.fired.get("stepper") == 1
        assert sum(ex.stepper_restarts for ex in c.executors) == 1
    finally:
        rt.shutdown(drain=True, timeout=60.0)


@slow
def test_dispatch_fault_retries_to_completion():
    faults = FaultInjector(schedule={"dispatch": [1]})
    c = _wall_cluster(EngineExecutorConfig(max_batch=4, max_len=48,
                                           decode_block=4, faults=faults))
    rt = ServingRuntime(c)
    try:
        h = rt.submit(_spec(np.random.default_rng(1)))
        res = h.result(timeout=600.0)
        assert res.ok and res.attempts > 1
        assert faults.fired.get("dispatch") == 1
    finally:
        rt.shutdown(drain=True, timeout=60.0)


@slow
def test_deadline_enforce_surfaces_timed_out_verdict():
    """A 1 ms SLO cannot survive the first real decode step: the engine
    cancels the slot, the verdict rides executor -> worker -> api, and
    the handle resolves (not failed, not wedged) with timed_out=True."""
    c = _wall_cluster(EngineExecutorConfig(max_batch=4, max_len=48,
                                           decode_block=4,
                                           deadline_enforce=True))
    rt = ServingRuntime(c)
    try:
        h = rt.submit(_spec(np.random.default_rng(2), max_new=30,
                            latency_ms=1))
        res = h.result(timeout=600.0)
        assert res.timed_out is True
        assert not res.failed, "timeout is a verdict, not a failure"
        # and a comfortable deadline stays un-flagged on the same cluster
        h2 = rt.submit(_spec(np.random.default_rng(3)))
        res2 = h2.result(timeout=600.0)
        assert res2.ok and res2.timed_out is False
    finally:
        rt.shutdown(drain=True, timeout=60.0)


def test_stepper_exception_reaches_failed_result(monkeypatch, caplog):
    """An engine exception on the threaded stepper still fails the query
    once its retries are spent, but its type and message now reach the
    result, and the worker logs the traceback."""
    from repro.serving.engine import ServingEngine

    def boom(self):
        raise RuntimeError("boom")

    monkeypatch.setattr(ServingEngine, "step", boom)
    cfg = MasterConfig(worker_autoscale=False, max_retries=1,
                       retry_delay=0.05)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real", clock="wall",
                     engine_cfg=EngineExecutorConfig(max_batch=2, max_len=32,
                                                     decode_block=4))
    rt = ServingRuntime(c)
    try:
        with caplog.at_level("ERROR", logger="repro.core.worker"):
            res = rt.submit(_spec(np.random.default_rng(4))).result(
                timeout=600.0)
        assert res.failed and not res.ok
        assert res.attempts == 2
        assert res.error == "RuntimeError: boom"
        assert sum(ex.stepper_restarts for ex in c.executors) == 2
        logged = [r for r in caplog.records if r.exc_info is not None]
        assert logged and all("boom" in str(r.exc_info[1]) for r in logged)
    finally:
        rt.shutdown(drain=True, timeout=60.0)
