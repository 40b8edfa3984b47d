"""Streaming semantics (ISSUE 8): partial outputs at decode-segment
granularity must be a pure *view* of the exact same generation —

* chunks are delivered in emission order,
* concatenating a request's chunks is bit-identical to its final tokens
  (and to a non-streaming engine's output), across dense / ssm / hybrid
  families on both KV layouts (contiguous + paged),
* time-to-first-token is monotone: arrival <= first_token <= completion,
* a preempted-then-replayed request never re-streams tokens it already
  delivered (the ``Request.streamed`` cursor survives parking).
"""
import numpy as np
import pytest

import jax

from repro.configs.registry import ARCHS
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine

# every test here builds real JAX models
pytestmark = pytest.mark.slow

_BUILT = {}


def _build(arch):
    if arch not in _BUILT:
        cfg = ARCHS[arch].reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _BUILT[arch] = (cfg, model, params)
    return _BUILT[arch]


def _stream(cfg, n=6, seed=7, max_new=(4, 10)):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(3, 10))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(*max_new)))
            for i in range(n)]


def _run_streaming(eng, reqs):
    """Drive to drain, returning chunks as (rid, tokens, t) in the order
    the engine emitted them."""
    for r in reqs:
        eng.submit(r)
    chunks = []
    while eng.busy:
        eng.step()
        for r, toks, t in eng.drain_partial_outputs():
            chunks.append((r.rid, list(toks), t))
    eng.drain_completions()
    assert eng.drain_partial_outputs() == []
    return chunks


def _concat(chunks, rid):
    return [t for r, toks, _ in chunks if r == rid for t in toks]


@pytest.mark.parametrize("arch", ["llama3.2-1b", "xlstm-1.3b",
                                  "zamba2-1.2b"])
@pytest.mark.parametrize("page_size", [None, 8])
def test_stream_concat_bit_identical(arch, page_size):
    """Streamed chunks concatenate to exactly the final tokens, and
    enabling streaming does not perturb generation at all."""
    cfg, model, params = _build(arch)
    kw = dict(max_batch=3, max_len=64, decode_block=4, min_bucket=4)
    if page_size is not None:
        kw["page_size"] = page_size
    ref_engine = ServingEngine(model, params, **kw)
    ref = _stream(cfg)
    ref_engine.serve(ref)

    eng = ServingEngine(model, params, stream=True, **kw)
    got = _stream(cfg)
    chunks = _run_streaming(eng, got)
    assert chunks, "streaming engine emitted no partial outputs"
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(
            np.asarray(a.tokens), np.asarray(b.tokens),
            err_msg=f"stream=True changed rid={a.rid}")
        assert _concat(chunks, b.rid) == [int(x) for x in b.tokens], \
            f"chunk concat != final tokens for rid={b.rid}"


def test_stream_emission_order_and_ttft_monotone():
    cfg, model, params = _build("llama3.2-1b")
    eng = ServingEngine(model, params, stream=True, max_batch=3,
                        max_len=64, decode_block=4, min_bucket=4)
    got = _stream(cfg)
    chunks = _run_streaming(eng, got)
    ts = [t for _, _, t in chunks]
    assert ts == sorted(ts), "chunks not in emission order"
    for r in got:
        mine = [(toks, t) for rid, toks, t in chunks if rid == r.rid]
        assert mine, f"rid={r.rid} streamed nothing"
        assert r.first_token == mine[0][1], \
            "first_token must stamp the first chunk's harvest time"
        assert r.arrival <= r.first_token
        # completion wall time = arrival + latency
        assert r.first_token <= r.arrival + r.latency + 1e-6
        # max_new >= 4 with decode_block=4 < max_new for some requests:
        # at least the multi-segment requests see TTFT strictly before
        # completion (checked in aggregate below)
    multi = [r for r in got if r.max_new_tokens > 4]
    assert any(r.first_token < r.arrival + r.latency for r in multi)


@pytest.mark.parametrize("page_size", [None, 8])
def test_preempt_replay_never_restreams(page_size):
    """Preempt a slot after it has streamed at least one chunk; the
    replayed request must deliver only the tokens beyond its cursor —
    concat stays bit-identical with zero duplicates."""
    cfg, model, params = _build("llama3.2-1b")
    kw = dict(max_batch=2, max_len=64, decode_block=4, min_bucket=4)
    if page_size is not None:
        kw["page_size"] = page_size
    eng = ServingEngine(model, params, stream=True, **kw)
    got = _stream(cfg, max_new=(8, 12))
    for r in got:
        eng.submit(r)
    chunks = []
    victim = None
    while eng.busy:
        eng.step()
        for r, toks, t in eng.drain_partial_outputs():
            chunks.append((r.rid, list(toks), t))
        if victim is None:
            live = [s for s in range(eng.max_batch)
                    if eng._slot_req[s] is not None
                    and eng._slot_req[s].streamed > 0
                    and eng._slot_req[s].streamed
                    < eng._slot_req[s].max_new_tokens]
            if live:
                victim = eng._slot_req[live[0]]
                eng.preempt(live[0])
    eng.drain_completions()
    assert victim is not None, "no slot had streamed before preemption"
    assert victim.preemptions >= 1
    for r in got:
        cat = _concat(chunks, r.rid)
        assert cat == [int(x) for x in r.tokens], \
            f"rid={r.rid} re-streamed or dropped tokens across preemption"
        assert len(cat) == len(r.tokens)   # no duplicates slipped in


def test_streaming_through_control_plane_virtual_clock():
    """End to end under the deterministic EventLoop: an executor with
    ``stream=True`` pushes chunks through worker -> Query.on_tokens ->
    QueryHandle; callbacks arrive in order, replay to late subscribers,
    concat matches ``result().outputs``, and ``ttft`` <= latency."""
    from repro.core.api import QueryPayload, QuerySpec
    from repro.serving.executor import EngineExecutorConfig
    from repro.sim.cluster import make_cluster

    arch = ARCHS["llama3.2-1b"].reduced()
    ecfg = EngineExecutorConfig(max_batch=4, max_len=48, decode_block=4,
                                stream=True)
    c = make_cluster(n_accel=1, archs=[arch], autoscale=False,
                     backend="real", engine_cfg=ecfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, arch.vocab, size=6),
               rng.integers(0, arch.vocab, size=9)]
    h = c.api.submit(QuerySpec.arch(
        arch.name, latency_ms=600_000,
        payload=QueryPayload.of(prompts, max_new_tokens=10)))
    live = []
    h.on_tokens(live.append)
    res = h.result(timeout=600.0)
    assert res.ok and res.outputs is not None
    assert live, "no streamed chunks reached the handle"
    ts = [c.t for c in live]
    assert ts == sorted(ts)
    for idx, out in enumerate(res.outputs):
        cat = [t for c in live if c.input_idx == idx for t in c.tokens]
        assert cat == [int(x) for x in out]
    # a late subscriber replays the full history in the same order
    replay = []
    h.on_tokens(replay.append)
    assert replay == live
    assert h.chunks and len(h.chunks) == len(live)
    assert h.ttft is not None and 0.0 <= h.ttft <= res.latency + 1e-9
