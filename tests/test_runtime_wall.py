"""Wall-clock serving runtime (ISSUE 8 tentpole): the control plane on
``RealClock`` with engines stepped by a background thread, streaming
tokens as segments retire.

* flag/constructor validation is cheap and runs in the fast CI job;
* the end-to-end and stress tests build real JAX models (slow).
"""
import threading

import numpy as np
import pytest

from repro.configs.registry import ARCHS
from repro.core.api import QueryPayload, QuerySpec
from repro.serving.executor import EngineExecutorConfig
from repro.serving.runtime import ServingRuntime, ThreadedEngineExecutor
from repro.sim.cluster import make_cluster

LLAMA = ARCHS["llama3.2-1b"]
SMOKE = LLAMA.reduced()   # what the real-backend clusters serve
slow = pytest.mark.slow


def test_wall_clock_requires_real_backend():
    with pytest.raises(ValueError, match="backend='real'"):
        make_cluster(n_accel=1, archs=[LLAMA], clock="wall")
    with pytest.raises(ValueError, match="clock"):
        make_cluster(n_accel=1, archs=[LLAMA], clock="lunar")


def test_runtime_rejects_virtual_cluster():
    c = make_cluster(n_accel=1, archs=[LLAMA], autoscale=False)
    with pytest.raises(ValueError, match="wall"):
        ServingRuntime(c)


def test_threaded_executor_disables_engine_eviction():
    """LRU engine eviction assumes idle engines between jobs; a threaded
    executor's engines hold in-flight slots, so the cap must be lifted."""
    ex = ThreadedEngineExecutor({LLAMA.name: LLAMA.reduced()},
                                EngineExecutorConfig(max_engines=2))
    assert ex.cfg.max_engines is None
    ex.shutdown()


def _wall_cluster():
    ecfg = EngineExecutorConfig(max_batch=4, max_len=48, decode_block=4)
    return make_cluster(n_accel=1, archs=[SMOKE], autoscale=False,
                        backend="real", clock="wall", engine_cfg=ecfg)


def _spec(rng, n_prompts=1, max_new=10):
    vocab = SMOKE.vocab
    prompts = [rng.integers(0, vocab,
                            size=int(rng.integers(4, 12))).astype(np.int32)
               for _ in range(n_prompts)]
    return QuerySpec.arch(SMOKE.name, latency_ms=600_000,
                          payload=QueryPayload.of(prompts,
                                                  max_new_tokens=max_new))


@slow
def test_wall_end_to_end_streaming():
    """Live submission from a client thread: tokens stream as segments
    retire, streamed concat is bit-identical to ``result().outputs``,
    TTFT lands at or before completion, and shutdown drains clean."""
    c = _wall_cluster()
    rt = ServingRuntime(c)
    try:
        rng = np.random.default_rng(0)
        handles = [rt.submit(_spec(rng, n_prompts=2)) for _ in range(4)]
        # iter_tokens on a live handle blocks on the cv (wall path)
        it_chunks = list(handles[0].iter_tokens(timeout=600.0))
        results = [h.result(timeout=600.0) for h in handles]
        assert all(r.ok for r in results), \
            [(r.failed, r.variant) for r in results]
        assert it_chunks and [c_.t for c_ in it_chunks] == \
            sorted(c_.t for c_ in it_chunks)
        for h, r in zip(handles, results):
            assert h.chunks, "no streamed chunks"
            for idx, out in enumerate(r.outputs):
                cat = [t for ch in h.chunks if ch.input_idx == idx
                       for t in ch.tokens]
                assert cat == [int(x) for x in out], \
                    "streamed concat != result() outputs"
            assert h.ttft is not None
            assert 0.0 <= h.ttft <= r.latency + 1e-9
    finally:
        assert rt.shutdown(drain=True, timeout=60.0)


@slow
def test_wall_worker_hang_mid_stream_retries_and_streams_exact():
    """ISSUE 10 satellite: a worker that hangs mid-stream under RealClock
    strands its in-flight query until the heartbeat sweep fires; the
    retry lands on the surviving worker and the client's streamed concat
    still equals the final outputs exactly: each attempt re-streams from
    token 0 with absolute offsets, and the handle trims the replayed
    prefix against its per-input cursor, so the dead attempt's partial
    chunks are never duplicated."""
    ecfg = EngineExecutorConfig(max_batch=4, max_len=48, decode_block=4)
    c = make_cluster(n_accel=2, archs=[SMOKE], autoscale=False,
                     backend="real", clock="wall", engine_cfg=ecfg)
    rt = ServingRuntime(c)
    try:
        rng = np.random.default_rng(7)
        h = rt.submit(_spec(rng, n_prompts=1, max_new=12))
        # hang the worker the query landed on as soon as it has started
        import time
        victim = None
        for _ in range(600):
            busy = [n for n, w in c.master.workers.items()
                    if any(li.pending or li.outstanding
                           for li in w.instances.values())]
            if busy:
                victim = busy[0]
                break
            time.sleep(0.05)
        assert victim is not None, "query never reached a worker"
        c.master.workers[victim].hang()
        res = h.result(timeout=600.0)
        assert res.ok, (res.failed, res.variant)
        assert res.attempts > 1, "hang never forced a retry"
        assert not c.store.workers[victim].alive, \
            "heartbeat sweep missed the hung worker"
        for idx, out in enumerate(res.outputs):
            cat = [t for ch in h.chunks if ch.input_idx == idx
                   for t in ch.tokens]
            assert cat == [int(x) for x in out], \
                "streamed concat != outputs after mid-stream retry"
    finally:
        rt.shutdown(drain=True, timeout=60.0)


@slow
def test_wall_submit_rejects_oversized_prompt():
    """A rejected job surfaces as a failed query, not a hung handle: the
    stepper validates before submitting and reports through on_done."""
    c = _wall_cluster()
    rt = ServingRuntime(c)
    try:
        vocab = SMOKE.vocab
        too_long = np.arange(60, dtype=np.int32) % vocab   # > max_len 48
        h = rt.submit(QuerySpec.arch(
            SMOKE.name, latency_ms=600_000,
            payload=QueryPayload.of([too_long], max_new_tokens=4)))
        res = h.result(timeout=120.0)
        assert res.failed and not res.ok
    finally:
        rt.shutdown(drain=True, timeout=60.0)


@slow
def test_threaded_executor_two_thread_stress():
    """Satellite 2 acceptance: two threads hammer ``run_async`` while the
    stepper drains — every job completes exactly once, every request's
    outputs are delivered exactly once, nothing is lost or duplicated."""
    ex = ThreadedEngineExecutor(
        {LLAMA.name: LLAMA.reduced()},
        EngineExecutorConfig(max_batch=4, max_len=48, decode_block=4,
                             stream=True))
    from repro.core import profiler as prof
    variant = next(v for v in prof.generate_variants(LLAMA)
                   if v.hardware in ("cpu-host", "tpu-v5e-1"))
    vocab = LLAMA.reduced().vocab
    n_per_thread = 8
    lock = threading.Lock()
    done = []          # (thread, job_idx, duration | error)
    outputs = {}       # (thread, job_idx) -> delivery count

    def hammer(tid):
        from repro.core.worker import ExecRequest
        rng = np.random.default_rng(tid)
        for j in range(n_per_thread):
            key = (tid, j)

            def on_outputs(outs, key=key):
                with lock:
                    outputs[key] = outputs.get(key, 0) + 1

            def on_done(duration, error=None, key=key):
                with lock:
                    done.append((key, duration, error))

            prompt = rng.integers(0, vocab, size=int(rng.integers(4, 10)))
            er = ExecRequest(n_inputs=1,
                             prompts=(tuple(int(x) for x in prompt),),
                             max_new_tokens=int(rng.integers(2, 8)),
                             on_outputs=on_outputs)
            ex.run_async(variant, 1, [er], on_done)

    threads = [threading.Thread(target=hammer, args=(tid,))
               for tid in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ex.shutdown(timeout=300.0)     # drains every queued job before stopping

    total = 2 * n_per_thread
    assert len(done) == total, f"lost/duplicated completions: {done}"
    assert all(err is None for _, _, err in done), done
    assert len({key for key, _, _ in done}) == total, "duplicate on_done"
    assert set(outputs) == {(t, j) for t in range(2)
                            for j in range(n_per_thread)}
    assert all(n == 1 for n in outputs.values()), "outputs delivered twice"
    # after the drain nothing is left in flight
    assert not ex._active and not ex._sinks and not ex._req_job


@slow
def test_cobatched_jobs_split_occupancy():
    """Two jobs co-batched on one engine split each shared segment's
    occupancy by live-slot share: per-job deltas are fractions that
    *conserve* the engine's raw counters (the old whole-delta-per-job
    accounting double-billed shared segments, summing to ~2x)."""
    from repro.core import profiler as prof
    from repro.core.worker import ExecRequest

    ex = ThreadedEngineExecutor(
        {LLAMA.name: LLAMA.reduced()},
        EngineExecutorConfig(max_batch=4, max_len=48, decode_block=4))
    variant = next(v for v in prof.generate_variants(LLAMA)
                   if v.hardware in ("cpu-host", "tpu-v5e-1"))
    vocab = LLAMA.reduced().vocab
    rng = np.random.default_rng(0)
    done = threading.Event()
    finished = []

    def on_done(duration, error=None):
        finished.append((duration, error))
        if len(finished) == 2:
            done.set()

    def job():
        prompts = tuple(tuple(int(x) for x in
                              rng.integers(0, vocab, size=6))
                        for _ in range(2))
        return [ExecRequest(n_inputs=2, prompts=prompts,
                            max_new_tokens=12)]

    # both queued before the stepper drains its queue -> they co-batch
    ex.run_async(variant, 2, job(), on_done)
    ex.run_async(variant, 2, job(), on_done)
    assert done.wait(300.0)
    ex.shutdown(timeout=60.0)
    assert all(err is None for _, err in finished), finished

    eng = ex.engines[variant.name]
    recs = list(ex.occupancy_log)
    assert len(recs) == 2
    # conservation: per-job shares sum back to the engine's raw counters
    assert sum(r["segments"] for r in recs) == \
        pytest.approx(eng.stats["decode_dispatches"])
    assert sum(r["bubble_slot_steps"] for r in recs) == \
        pytest.approx(eng.stats["bubble_slot_steps"])
    # both jobs were really live together and got billed something
    assert all(r["segments"] > 0 for r in recs)
    # no job was billed the whole engine history (the over-attribution
    # this split removes): each saw only its share of shared segments
    assert all(r["segments"] < eng.stats["decode_dispatches"]
               for r in recs)
