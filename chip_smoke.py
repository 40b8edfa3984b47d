#!/usr/bin/env python3
"""Bring-up smoke: phi3-mini-3.8b at published widths on one TPU chip,
through the served path (QuerySpec -> master -> worker -> EngineExecutor
-> ServingEngine).

    python chip_smoke.py

Phases, in one process (a child would find the chip held):

1. device: JAX's first device must be a TPU; there is no CPU fallback.
2. control plane on the virtual clock: 8 seeded payload queries at arch
   granularity, one after another; every one must resolve ok with 32
   int32 tokens on a ``tpu-v5e-1`` variant, equal token for token to a
   fresh ``ServingEngine`` driven directly on the same params and
   geometry with the same prompt alone. (On the chip a row's logits
   depend on what it is co-batched with, since the matmul's M changes
   with the prefill group, and random weights leave many near-ties in
   the argmax, so the comparison holds the batch composition equal.)
3. wall-clock server: 4 streamed queries through ``ServingRuntime``; each
   resolves ok with a TTFT and its streamed chunks concatenate to its
   outputs; then a clean shutdown.

Weights are random (seeded). Set-up (params, compiles, one warm-up query)
is timed apart from serving. The tok/s it prints is a smoke number from
one short run, not a benchmark. Any failure prints its cause and exits
non-zero; on success the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import gc
import json
import logging
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "phi3-mini-3.8b"
N_VIRTUAL, N_WALL = 8, 4
PROMPT_LENS = (65, 128)      # one prefill bucket (128)
MAX_NEW = 32
LATENCY_MS = 600_000.0       # generous: selection, not the SLO, is tested
WALL_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def engine_config():
    from repro.serving.executor import EngineExecutorConfig
    # contiguous KV, XLA attention (the config defaults); min_bucket and
    # prompt_len pin warm-up and every prompt to the one 128 bucket
    return EngineExecutorConfig(max_batch=4, max_len=1024, decode_block=16,
                                min_bucket=128, prompt_len=128,
                                max_new=MAX_NEW)


def prompts(cfg, n, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=int(rng.integers(
        PROMPT_LENS[0], PROMPT_LENS[1] + 1))).astype(np.int32)
        for _ in range(n)]


def spec(cfg, prompt):
    from repro.core.api import QueryPayload, QuerySpec
    return QuerySpec.arch(cfg.name, latency_ms=LATENCY_MS,
                          payload=QueryPayload.of([prompt],
                                                  max_new_tokens=MAX_NEW))


def tree_bytes(*trees):
    import jax
    seen, n = set(), 0
    for leaf in jax.tree.leaves(trees):
        if id(leaf) not in seen:
            seen.add(id(leaf))
            n += leaf.nbytes
    return n


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"{stats.get('peak_bytes_in_use', 'not reported')} "
            f"(bytes_limit {stats.get('bytes_limit', 'not reported')})")


def check_results(cluster, results, hardware):
    """Every query ok, 32 int32 tokens, served by a ``hardware`` variant
    on a non-interpret config."""
    import numpy as np
    for i, r in enumerate(results):
        check(r.ok, f"query {i} failed after {r.attempts} attempt(s): "
                    f"{r.error or 'no executor error recorded'}")
        check(r.outputs is not None and len(r.outputs) == 1,
              f"query {i}: outputs {r.outputs!r}")
        out = r.outputs[0]
        check(out.dtype == np.int32 and out.shape == (MAX_NEW,),
              f"query {i}: output {out.dtype}{out.shape}, want "
              f"int32({MAX_NEW},)")
        v = cluster.store.registry.variants[r.variant]
        check(v.hardware == hardware,
              f"query {i} served by {r.variant} on {v.hardware}")
    for ex in cluster.executors:
        for name, eng in ex.engines.items():
            mc = eng.model.cfg
            check(not mc.attention_impl.endswith("_interpret")
                  and not mc.quantize.endswith("_interpret"),
                  f"{name} runs an interpret-mode kernel "
                  f"({mc.attention_impl}, {mc.quantize})")
            print(f"  engine {name}: attention={mc.attention_impl} "
                  f"quantize={mc.quantize} kv="
                  f"{'paged' if eng._paged else 'contiguous'} "
                  f"max_batch={eng.max_batch} max_len={eng.max_len} "
                  f"prefill_traces={eng.stats['prefill_traces']} "
                  f"decode_traces={eng.stats['decode_traces']}")


def reference_tokens(ex, variant, geometry, ps):
    """The same prompts through a fresh engine on the executor's params,
    with the served engine's geometry."""
    import numpy as np
    from repro.serving.engine import Request, ServingEngine
    model, params = ex._model(
        variant.arch, "int8" if variant.framework == "jax-int8" else "none")
    eng = ServingEngine(model, params, **geometry)
    out = []
    for i, p in enumerate(ps):
        req = Request(rid=i, prompt=np.asarray(p, np.int32),
                      max_new_tokens=MAX_NEW)
        eng.serve([req])
        out.append(np.asarray(req.tokens, np.int32))
    return out


def serve_virtual(cfg, ecfg, hardware="tpu-v5e-1"):
    """Phase 2. Returns the number of queries checked against the
    direct engine."""
    import numpy as np
    from repro.core.master import MasterConfig
    from repro.sim.cluster import make_cluster

    cluster = make_cluster(n_accel=1, n_cpu=0, archs=[cfg], backend="real",
                           autoscale=False,
                           cfg=MasterConfig(worker_autoscale=False),
                           engine_cfg=ecfg)
    ps = prompts(cfg, N_VIRTUAL + 1)
    warm, ps = ps[0], ps[1:]
    t0 = time.perf_counter()
    r = cluster.api.submit(spec(cfg, warm)).result(timeout=1e9)
    check(r.ok, f"warm-up query failed: {r.error}")
    setup_s = time.perf_counter() - t0
    (ex,) = cluster.executors
    traces0 = sum(e.stats["prefill_traces"] + e.stats["decode_traces"]
                  for e in ex.engines.values())
    t0 = time.perf_counter()
    results = [cluster.api.submit(spec(cfg, p)).result(timeout=1e9)
               for p in ps]
    serve_s = time.perf_counter() - t0
    traces = sum(e.stats["prefill_traces"] + e.stats["decode_traces"]
                 for e in ex.engines.values()) - traces0
    for i, r in enumerate(results):
        print(f"  query {i}: prompt {len(ps[i])} tokens -> {r.variant} "
              f"ok={r.ok} attempts={r.attempts}")
    check_results(cluster, results, hardware)
    toks = sum(len(r.outputs[0]) for r in results)
    print(f"  set-up (params + compile + warm-up query): {setup_s:.3f} s; "
          f"serve: {serve_s:.3f} s, {traces} new traces inside")
    print(f"  generated {toks} tokens; smoke tok/s (one run, not a "
          f"benchmark): {toks / serve_s:.1f}")
    print(f"  weights on device: "
          f"{tree_bytes(*(p for _, p in ex._models.values())) / 1e9:.3f} "
          f"GB "
          f"({', '.join(sorted(ex._models))})")

    by_variant = {}
    for i, r in enumerate(results):
        by_variant.setdefault(r.variant, []).append(i)
    geometry = {name: dict(max_batch=e.max_batch, max_len=e.max_len,
                           decode_block=e.decode_block,
                           min_bucket=e.min_bucket)
                for name, e in ex.engines.items()}
    ex.engines.clear()      # the reference engines need their HBM
    matched = 0
    for name, idx in by_variant.items():
        v = cluster.store.registry.variants[name]
        ref = reference_tokens(ex, v, geometry[name], [ps[i] for i in idx])
        for i, want in zip(idx, ref):
            got = results[i].outputs[0]
            check(np.array_equal(got, want),
                  f"query {i} ({name}): served {got.tolist()} != direct "
                  f"engine {want.tolist()}")
            matched += 1
    print(f"  served tokens == direct-engine reference for "
          f"{matched}/{len(results)} queries")
    print(f"  peak_bytes_in_use: {peak_bytes()}")
    return matched


def serve_wall(cfg, ecfg, hardware="tpu-v5e-1"):
    """Phase 3: ``N_WALL`` concurrent queries streamed through the
    wall-clock server."""
    from repro.serving.runtime import ServingRuntime
    from repro.sim.cluster import make_cluster

    cluster = make_cluster(n_accel=1, n_cpu=0, archs=[cfg], backend="real",
                           autoscale=False, engine_cfg=ecfg, clock="wall")
    rt = ServingRuntime(cluster)
    ps = prompts(cfg, N_VIRTUAL + 1)
    warm, ps = ps[0], ps[1:N_WALL + 1]
    try:
        t0 = time.perf_counter()
        r = rt.submit(spec(cfg, warm)).result(timeout=WALL_TIMEOUT_S)
        check(r.ok, f"warm-up query failed: {r.error}")
        setup_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        handles = [rt.submit(spec(cfg, p)) for p in ps]
        results = [h.result(timeout=WALL_TIMEOUT_S) for h in handles]
        serve_s = time.perf_counter() - t0
        check_results(cluster, results, hardware)
        for i, (h, r) in enumerate(zip(handles, results)):
            check(h.ttft is not None, f"query {i} streamed nothing")
            cat = [t for ch in h.chunks if ch.input_idx == 0
                   for t in ch.tokens]
            check(cat == r.outputs[0].tolist(),
                  f"query {i}: streamed {cat} != outputs "
                  f"{r.outputs[0].tolist()}")
            print(f"  query {i}: {r.variant} ttft={h.ttft:.4f} s "
                  f"latency={r.latency:.4f} s chunks={len(h.chunks)}")
        print(f"  set-up (params + compile + warm-up query): {setup_s:.3f} "
              f"s; serve: {serve_s:.3f} s for {len(results)} streamed "
              f"queries")
    finally:
        clean = rt.shutdown(drain=True, timeout=60.0)
    check(clean, "wall-clock server did not drain on shutdown")
    print(f"  clean shutdown; peak_bytes_in_use: {peak_bytes()}")


def live_device_bytes():
    import jax
    return sum(a.nbytes for a in jax.live_arrays())


def main() -> int:
    logging.basicConfig(level=logging.WARNING,
                        format="%(levelname)s %(name)s: %(message)s")
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    from repro.configs.registry import ARCHS
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache: {enable_compile_cache()}")
    cfg = ARCHS[ARCH]                    # published widths, no .reduced()
    print(f"arch {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv x {cfg.head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}; "
          f"{cfg.param_count() / 1e9:.3f} B params")
    ecfg = engine_config()
    phase = "control plane (virtual clock)"
    try:
        print(f"[{phase}]")
        serve_virtual(cfg, ecfg)
        gc.collect()
        print(f"  device bytes still live after teardown: "
              f"{live_device_bytes()}")
        phase = "wall-clock server"
        print(f"[{phase}]")
        serve_wall(cfg, ecfg)
    except Exception as e:  # noqa: BLE001 - report, then exit non-zero
        traceback.print_exc()
        print(f"FAILED in {phase}: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
