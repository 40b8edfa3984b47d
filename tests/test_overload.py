"""Selection's overload test and the master autoscaler's latency-spike test
compare like with like: the service time of one step of the profile's
t(b), queue excluded, against the profiled step.

A generative query takes one decode step per token it emits and waits in
the worker's queue behind whole jobs, so its end-to-end latency is seconds
where one profiled step is milliseconds. Set against a profiled step, it
would make every busy generative instance read as overloaded, and
variant-named queries to a Yi-9B stage, which leaves no room for a second
copy on its chip, would be rejected.

Each case drives a closed loop of 16 clients through
``make_cluster(backend="real")``: master -> selection -> worker ->
``EngineExecutor`` -> ``ServingEngine``, on reduced Yi-shaped (grouped
heads, bf16 variant) and phi3-shaped (one KV head per query head, int8
variant) models. The profile is first set to this host's speed: one query
alone, its service time over its tokens (a prefill plus decode steps, so
at least one decode step, and with a prefill shared by 8 tokens, a few).
"""
import dataclasses

import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.api import QueryPayload, QuerySpec
from repro.core.autoscaler import MasterAutoscaler, MasterScaleConfig
from repro.core.master import MasterConfig
from repro.serving.executor import EngineExecutorConfig
from repro.sim.cluster import make_cluster

YI = dataclasses.replace(ARCHS["yi-9b"].reduced(), name="yi-9b-l24-smoke")
PHI3 = ARCHS["phi3-mini-3.8b"].reduced()
VARIANTS = {"yi": (YI, "bf16-b8"), "phi3": (PHI3, "int8-b8")}
ECFG = EngineExecutorConfig(max_batch=8, max_len=64, decode_block=4,
                            min_bucket=8, prompt_len=8, max_new=4)
CLIENTS, QUERIES, NEW_TOKENS = 16, 48, 24
CAL_TOKENS = 8
# the slow worker's factor: far past the 1.5x and 2x tests, since this
# host's step (one query alone, prefill included) can read several times
# a bare decode step when the CPU is shared
SLOW = 50.0


def _closed_loop(model: str, slowdown: float):
    arch, suffix = VARIANTS[model]
    c = make_cluster(n_accel=1, archs=[arch], autoscale=False,
                     cfg=MasterConfig(worker_autoscale=False),
                     backend="real", engine_cfg=ECFG)
    v = c.store.variant(f"{arch.name}/tpu-v5e-1/{suffix}")
    (w,) = c.master.workers.values()
    rng = np.random.default_rng(7)

    def spec(new_tokens=NEW_TOKENS):
        prompt = rng.integers(0, arch.vocab, rng.integers(8, 24))
        return QuerySpec.variant(v.name, payload=QueryPayload.of(
            [prompt.astype(np.int32)], max_new_tokens=new_tokens))

    assert w.load_variant(v)
    c.run_until(v.profile.load_latency + 1.0)
    alone = c.api.submit(spec(CAL_TOKENS)).result(timeout=600.0)
    assert alone.ok
    # this host's step: the profile's t(b) is an accelerator's
    step = alone.compute / CAL_TOKENS
    v.profile.m, v.profile.c = 0.0, step
    v.profile.peak_qps = v.profile.max_batch / step
    w.slowdown = slowdown

    outcomes, handles = [], []
    select = c.master.selector.select_variant

    def recorded(*a, **kw):
        sel = select(*a, **kw)
        outcomes.append(sel.outcome)
        return sel
    c.master.selector.select_variant = recorded

    def client():
        if len(handles) < QUERIES:
            h = c.api.submit(spec())
            handles.append(h)
            h.add_done_callback(lambda _h: c.loop.schedule(0.0, client))
    for _ in range(CLIENTS):
        client()
    while not all(h.done for h in handles) or len(handles) < QUERIES:
        c.run_until(c.loop.now() + 5.0)
    assert all(h.result(timeout=0.0).ok for h in handles)
    # one more monitor window publishes the last jobs' step times
    c.run_until(c.loop.now() + 2.0 * w.cfg.monitor_period)
    inst = c.store.instance(v.name, w.name)
    return c, v, w, inst, outcomes


@pytest.mark.parametrize("model,case", [
    ("yi", "many_steps"), ("phi3", "many_steps"), ("yi", "slow_steps"),
    ("yi", "qps")])
def test_overload_compares_service_per_step(model, case):
    c, v, w, inst, outcomes = _closed_loop(
        model, slowdown=SLOW if case == "slow_steps" else 1.0)
    spike = MasterAutoscaler(c.store, c.loop, lambda *a: None,
                             lambda *a: None, MasterScaleConfig())
    assert inst.step_latency > 0
    if case == "many_steps":
        # queries of 24 tokens behind whole jobs: each took many profiled
        # steps end to end, and none of them found the instance overloaded
        assert len(outcomes) == QUERIES
        assert set(outcomes) <= {"running", "cache"}, outcomes
        assert not c.store.is_overloaded(inst)
        assert inst.step_latency <= 1.5 * v.profile.latency(v.batch_opt)
    elif case == "slow_steps":
        # a worker far slower per step than its profile reads as overloaded
        # (selection then places the next query on a fresh copy) and as a
        # latency spike
        assert c.store.slower_than_profile(inst, 1.5)
        assert c.store.is_overloaded(inst)
        assert c.master.selector.select_variant(v.name, 1).outcome == "load"
    else:
        # the QPS half of the predicate is unchanged
        assert not c.store.slower_than_profile(inst, 1.5)
        inst.qps = 0.95 * v.profile.peak_qps * inst.replicas
        assert c.store.is_overloaded(inst)
        inst.qps = 0.9 * v.profile.peak_qps * inst.replicas
        assert not c.store.is_overloaded(inst)
    # the autoscaler's latency-spike test is the same comparison at its
    # own factor
    factor = spike.cfg.latency_spike_factor
    assert spike._latency_spike(w.name) == \
        c.store.slower_than_profile(inst, factor)
    assert spike._latency_spike(w.name) == (case == "slow_steps")
