"""The Executor seam: control plane over real engines (ISSUE 2 tentpole),
and the payload path (ISSUE 3): a QuerySpec carrying real prompts served
through master -> worker -> EngineExecutor -> ServingEngine, generated
tokens returned via QueryHandle.result().

``make_cluster(backend="real")`` serves a mixed stream through
master -> variant selection -> ``EngineExecutor`` (real continuous-batching
engines on reduced configs), and measured service times re-fit variant
profiles in place — the closed loop between data plane and control plane.
"""
import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.api import QueryPayload, QuerySpec
from repro.core.master import MasterConfig
from repro.core.worker import Executor, SimExecutor
from repro.sim.cluster import make_cluster

LLAMA = ARCHS["llama3.2-1b"]
# real-backend clusters build their engines from the configs they are
# given: the CPU tests hand them the reduced smoke config
SMOKE = LLAMA.reduced()

# tests that build real JAX models are excluded from the fast CI job
slow = pytest.mark.slow


def _done(q):
    return q.finish >= 0 and not q.failed


def test_sim_executor_is_the_default_and_satisfies_protocol():
    c = make_cluster(n_accel=1, archs=[LLAMA], autoscale=False)
    w = next(iter(c.master.workers.values()))
    assert isinstance(w.executor, SimExecutor)
    assert isinstance(w.executor, Executor)
    v = next(iter(c.store.registry.variants.values()))
    assert w.executor.run(v, 4) == pytest.approx(v.profile.latency(4))


@slow
def test_real_backend_serves_and_calibrates_profiles():
    """End-to-end acceptance: a mixed stream runs through selection into
    real engines, and at least one variant's m/c is re-fit from measured
    service times."""
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    before = {v.name: (v.profile.m, v.profile.c)
              for v in c.store.registry.variants.values()}
    assert all(v.profile.source == "analytic"
               for v in c.store.registry.variants.values())
    # one early query (a batch-1 job), then a burst that the worker's
    # adaptive batching packs into a larger job -> two distinct batch
    # sizes observed -> refit
    qs = [c.api.online_query(mod_arch=SMOKE.name, latency_ms=600_000)]
    c.run_until(30.0)
    qs += [c.api.online_query(mod_arch=SMOKE.name, latency_ms=600_000)
           for _ in range(7)]
    c.run_until(300.0)
    assert all(_done(q) for q in qs), \
        [(q.qid, q.failed, q.finish) for q in qs]

    w = next(iter(c.master.workers.values()))
    ex = w.executor
    assert ex.engines, "no real engine was ever built"
    # real engines actually decoded tokens for every job
    assert sum(e.stats["tokens_generated"]
               for e in ex.engines.values()) > 0
    batches = {b for obs in ex.observations.values() for b in obs}
    assert len(batches) >= 2, batches

    measured = [v for v in c.store.registry.variants.values()
                if v.profile.source == "measured"]
    assert measured, "no profile was re-fit from measurements"
    for v in measured:
        assert (v.profile.m, v.profile.c) != before[v.name]
        assert v.profile.latency(1) > 0
        # peak_qps was recomputed against the measured fit
        assert v.profile.peak_qps == pytest.approx(
            v.profile.max_batch / v.profile.latency(v.profile.max_batch))


@slow
def test_real_backend_queries_see_measured_latency():
    """Virtual-clock query latency reflects real measured service time,
    not the analytic roofline guess."""
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    q = c.api.online_query(mod_arch=SMOKE.name, latency_ms=600_000)
    c.run_until(60.0)
    assert _done(q)
    w = next(iter(c.master.workers.values()))
    obs = [t for per_b in w.executor.observations.values()
           for ts in per_b.values() for t in ts]
    assert obs
    # service portion of the query latency equals a measured duration
    assert q.finish - q.start == pytest.approx(obs[0])


def test_usecase_query_redispatch_reselects():
    """Regression (ISSUE 2 satellite): a use-case query that cannot be
    placed yet must retry via select_usecase — not fail because it carries
    neither arch nor variant."""
    c = make_cluster(n_accel=0, n_cpu=0, archs=[LLAMA], autoscale=False)
    q = c.api.online_query(task="text-generation", dataset="openwebtext",
                           accuracy=0.5, latency_ms=600_000)
    assert q.task == "text-generation" and q.dataset == "openwebtext"
    # capacity appears only after the query has started retrying
    c.loop.schedule(0.6, lambda: c.master.add_worker("accel"))
    c.run_until(120.0)
    assert _done(q), (q.failed, q.finish)
    assert q.variant


def test_variant_query_redispatch_reselects():
    """Same hole as above for variant-named queries: the user's mod_var
    choice must survive a failed first dispatch and retry."""
    c = make_cluster(n_accel=0, n_cpu=0, archs=[LLAMA], autoscale=False)
    vname = next(v.name for v in c.store.registry.variants.values()
                 if v.hardware == "tpu-v5e-1")
    q = c.api.online_query(mod_var=vname, latency_ms=600_000)
    assert q.variant == vname
    c.loop.schedule(0.6, lambda: c.master.add_worker("accel"))
    c.run_until(120.0)
    assert _done(q), (q.failed, q.finish)
    assert q.variant == vname


def test_variant_objects_stay_hashable():
    """The frozen Variant hashes its (identity-hashed, mutable) profile;
    sets/dict keys of Variants must keep working."""
    c = make_cluster(n_accel=1, archs=[LLAMA], autoscale=False)
    vs = list(c.store.registry.variants.values())
    assert len({v for v in vs}) == len(vs)
    assert vs[0] in {vs[0]}


@slow
def test_jax_executor_measured_keyed_by_prompt_len():
    """Regression (ISSUE 2 satellite): mixed-length calibration runs must
    not overwrite each other."""
    from repro.serving.engine import JaxExecutor
    ex = JaxExecutor({LLAMA.name: LLAMA.reduced()},
                     max_batch=2, max_len=32, decode_block=4, min_bucket=4)
    ex.execute(LLAMA.name, batch=2, prompt_len=4, max_new=2)
    ex.execute(LLAMA.name, batch=2, prompt_len=8, max_new=2)
    keys = set(ex.measured)
    assert keys == {(LLAMA.name, 2, 4), (LLAMA.name, 2, 8)}
    assert all(t > 0 for t in ex.measured.values())


# ----------------------------------------------------------------------
# ISSUE 3 acceptance: a real multi-prompt payload flows client -> master ->
# worker -> EngineExecutor -> ServingEngine and the generated token ids
# come back through QueryHandle.result(), bit-identical to driving the
# engine directly with the same prompts.
PROMPTS = ((3, 1, 4, 1, 5, 9), (2, 7, 1, 8), (1, 6, 1, 8, 0, 3, 3, 9))
MAX_NEW = 4


@slow
def test_real_payload_outputs_bit_identical_to_direct_engine():
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    spec = QuerySpec.usecase(
        "text-generation", "openwebtext", min_accuracy=0.5,
        latency_ms=600_000,
        payload=QueryPayload.of(PROMPTS, max_new_tokens=MAX_NEW))
    h = c.api.submit(spec)
    res = h.result(timeout=600.0)
    assert res.ok, (res.failed, res.variant)
    assert res.outputs is not None and len(res.outputs) == len(PROMPTS)
    for out in res.outputs:
        assert out.dtype == np.int32 and len(out) == MAX_NEW

    # drive a FRESH engine (same shared model/params, same geometry)
    # directly with the same prompts: outputs must match token for token
    w = next(iter(c.master.workers.values()))
    ex = w.executor
    variant = c.store.registry.variants[res.variant]
    exec_eng = ex.engines[variant.name]
    model, params = ex._model(
        variant.arch, "int8" if variant.framework == "jax-int8" else "none")
    from repro.serving.engine import Request, ServingEngine
    eng = ServingEngine(model, params, max_batch=exec_eng.max_batch,
                        max_len=exec_eng.max_len,
                        decode_block=exec_eng.decode_block,
                        min_bucket=exec_eng.min_bucket)
    reqs = [Request(rid=i, prompt=np.asarray(p, np.int32),
                    max_new_tokens=MAX_NEW)
            for i, p in enumerate(PROMPTS)]
    eng.serve(reqs)
    for r, out in zip(reqs, res.outputs):
        np.testing.assert_array_equal(r.tokens, out)


@slow
def test_real_offline_payload_produces_outputs():
    """Offline payloads are sliced chunk by chunk into the real engine and
    their outputs accumulate on the job in input order."""
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    prompts = tuple(tuple(int(x) for x in np.arange(2 + (i % 3)) + i)
                    for i in range(6))
    h = c.api.submit(QuerySpec.arch(
        SMOKE.name, mode="offline",
        payload=QueryPayload.of(prompts, max_new_tokens=2)))
    res = h.result(timeout=600.0)
    assert res.ok and res.processed >= len(prompts)
    assert len(h.job.outputs) == len(prompts)
    for out in h.job.outputs:
        assert len(out) == 2


def test_sim_backend_payload_is_accounted_not_executed():
    """On the sim backend a payload shapes n_inputs/batching but produces
    no outputs — the simulator has no tokens to return."""
    c = make_cluster(n_accel=1, archs=[LLAMA], autoscale=False)
    h = c.api.submit(QuerySpec.arch(
        LLAMA.name, latency_ms=600_000,
        payload=QueryPayload.of(PROMPTS, max_new_tokens=MAX_NEW)))
    res = h.result(timeout=120.0)
    assert res.ok
    assert h.query.n_inputs == len(PROMPTS)
    assert res.outputs is None


@slow
def test_oversized_payload_fails_query_without_wedging_device():
    """A payload exceeding the real engine's max_len must fail the query
    (not leak a ValueError into the event loop) and leave the device
    usable for subsequent queries."""
    cfg = MasterConfig(worker_autoscale=False, max_retries=1,
                       retry_delay=0.1)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    bad = c.api.submit(QuerySpec.arch(
        SMOKE.name, latency_ms=600_000,
        payload=QueryPayload.of([list(range(40))], max_new_tokens=4)))
    res = bad.result(timeout=300.0)
    assert res.failed and not res.ok
    # the device slot was not leaked: a normal query still completes
    ok = c.api.submit(QuerySpec.arch(SMOKE.name, latency_ms=600_000))
    assert ok.result(timeout=300.0).ok


@slow
def test_real_backend_without_payload_returns_no_outputs():
    """Synthetic stand-in prompts are accounting, not answers: a
    payload-less query on the real backend must not surface their
    decoded tokens as outputs."""
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    h = c.api.submit(QuerySpec.arch(SMOKE.name, latency_ms=600_000))
    res = h.result(timeout=300.0)
    assert res.ok and res.outputs is None


@slow
def test_oversized_offline_payload_fails_once_not_forever():
    """A poisoned offline chunk must fail the job and leave the worker's
    offline queue — not be retried on every monitor tick."""
    cfg = MasterConfig(worker_autoscale=False)
    c = make_cluster(n_accel=1, archs=[SMOKE], autoscale=False, cfg=cfg,
                     backend="real")
    h = c.api.submit(QuerySpec.arch(
        SMOKE.name, mode="offline",
        payload=QueryPayload.of([list(range(40))], max_new_tokens=4)))
    res = h.result(timeout=300.0)
    assert res.failed and h.job.failed
    w = next(iter(c.master.workers.values()))
    assert h.job not in w.offline_jobs
    # and the cluster still serves normal traffic afterwards
    ok = c.api.submit(QuerySpec.arch(SMOKE.name, latency_ms=600_000))
    assert ok.result(timeout=300.0).ok


def test_payload_runs_do_not_refit_profiles():
    """Payload measurements have arbitrary prompt/decode shapes and must
    stay out of the synthetic t(b) calibration."""
    from repro.core import profiler as prof
    from repro.core.worker import ExecRequest
    from repro.serving.executor import EngineExecutor, EngineExecutorConfig

    class _NoRunEngine:
        busy = False
        stats = {"busy_slot_steps": 0, "bubble_slot_steps": 0,
                 "inseg_admissions": 0, "decode_dispatches": 0,
                 "preemptions": 0, "pressure_stalls": 0,
                 "prefix_hits": 0, "prefix_pages_reused": 0,
                 "cow_copies": 0, "evictions": 0}

        def warmup(self, prompt_lens=()):
            pass

        def submit(self, r):
            r.tokens = np.zeros(1, np.int32)

        def step(self):
            return 0

        def drain_completions(self):
            return []

    ex = EngineExecutor({LLAMA.name: LLAMA.reduced()},
                        EngineExecutorConfig())
    v = next(iter(prof.generate_variants(LLAMA)))
    ex.engines[v.name] = _NoRunEngine()
    ex.run(v, 2, [ExecRequest(n_inputs=2, prompts=((1, 2), (3,)),
                              max_new_tokens=1)])
    assert v.name not in ex.observations          # payload run: excluded
    ex.run(v, 2, [ExecRequest(n_inputs=2)])
    assert list(ex.observations[v.name]) == [2]   # synthetic run: recorded


@slow
def test_engine_executor_lru_eviction_caps_engines():
    """ISSUE 4 satellite: with ``max_engines`` set the per-variant engine
    map is an LRU — building past the cap evicts the least-recently-used
    engine, an evicted variant rebuilds lazily (and re-warms outside the
    measured window), and outputs stay correct after the round trip."""
    from repro.core import profiler as prof
    from repro.core.worker import ExecRequest
    from repro.serving.executor import EngineExecutor, EngineExecutorConfig

    ex = EngineExecutor({LLAMA.name: LLAMA.reduced()},
                        EngineExecutorConfig(max_engines=2, max_batch=2,
                                             max_len=16, decode_block=2,
                                             min_bucket=4, prompt_len=4,
                                             max_new=2))
    v1, v2, v3 = list(prof.generate_variants(LLAMA))[:3]
    ex.run(v1, 1)
    ex.run(v2, 1)
    assert set(ex.engines) == {v1.name, v2.name} and ex.evictions == 0
    ex.run(v3, 1)                       # v1 is the LRU victim
    assert set(ex.engines) == {v2.name, v3.name}
    assert ex.evictions == 1
    # touching v2 marks it most-recent: the next build evicts v3, not v2
    ex.run(v2, 1)
    ex.run(v1, 1)                       # lazy rebuild of the evictee
    assert set(ex.engines) == {v2.name, v1.name}
    assert ex.evictions == 2
    # rebuilt engine still serves real payloads correctly
    outs = []
    ex.run(v1, 1, [ExecRequest(n_inputs=1, prompts=((1, 2, 3),),
                               max_new_tokens=2,
                               on_outputs=outs.append)])
    assert len(outs) == 1 and len(outs[0][0]) == 2


@slow
def test_engine_executor_paged_knobs_reach_engines():
    """page_size / n_pages / chunk_threshold flow through the executor
    into every lazily-built engine."""
    from repro.core import profiler as prof
    from repro.serving.executor import EngineExecutor, EngineExecutorConfig

    ex = EngineExecutor({LLAMA.name: LLAMA.reduced()},
                        EngineExecutorConfig(max_batch=2, max_len=16,
                                             decode_block=2, min_bucket=4,
                                             prompt_len=4, max_new=2,
                                             page_size=8,
                                             chunk_threshold=8))
    v = next(iter(prof.generate_variants(LLAMA)))
    ex.run(v, 1)
    eng = ex.engines[v.name]
    assert eng._paged and eng.page_size == 8
    assert eng.chunk_threshold == 8
    assert eng.n_pages == eng.max_batch * eng.max_len // 8
