"""End-to-end latency: open-loop vs closed-loop serving on a real engine.

Replays the same mixed Poisson request stream (mixed prompt lengths and
decode lengths) against two identical continuous-batching engines driven
two ways:

* ``closed`` — the PR-1 loop: requests that arrive while ``serve()`` is
  running wait for the current batch to fully drain, then the backlog is
  served as the next batch. Admission only happens at serve() boundaries.
* ``open``   — the step-driven core: arrivals are ``submit()``-ed as they
  occur and join at the next decode-segment boundary (``step()``), without
  waiting for in-flight requests to finish.

Per-request latency is measured from the request's (replayed) arrival
time, so the closed loop pays its batch-drain queueing delay and the open
loop only pays segment granularity. The arrival rate is calibrated to the
engine's measured capacity (offered load ~ capacity), where the difference
is starkest. Both engines are warmed up first; no compile time is inside
the measured window.

Writes ``BENCH_e2e_real.json`` at the repo root.

Run:  PYTHONPATH=src python benchmarks/fig_e2e_real.py
"""
from __future__ import annotations

import json
import os
import time
from typing import List, Tuple

import jax
import numpy as np

Row = Tuple[str, float, str]

N_REQS = 24
SLOTS = 4
MAX_LEN = 64
DECODE_BLOCK = 4
PROMPT_RANGE = (4, 17)
MAX_NEW_RANGE = (4, 25)
UTILIZATION = 1.0      # offered load as a fraction of measured capacity


def _stream(cfg, seed: int, n: int = N_REQS):
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(*PROMPT_RANGE))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(*MAX_NEW_RANGE)))
            for i in range(n)]


def _arrival_offsets(rate: float, n: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def _drive_open(eng, reqs, offsets) -> List:
    """Submit each request at its arrival offset; step whenever busy."""
    done: List = []
    t0 = time.perf_counter()
    i = 0
    while len(done) < len(reqs):
        now = time.perf_counter() - t0
        while i < len(reqs) and offsets[i] <= now:
            reqs[i].arrival = t0 + offsets[i]
            eng.submit(reqs[i])
            i += 1
        if eng.busy:
            eng.step()
            done.extend(eng.drain_completions())
        elif i < len(reqs):
            time.sleep(max(offsets[i] - (time.perf_counter() - t0), 0.0))
    return done


def _drive_closed(eng, reqs, offsets) -> List:
    """PR-1 loop: arrivals during serve() wait for the batch to drain."""
    done: List = []
    t0 = time.perf_counter()
    i = 0
    while len(done) < len(reqs):
        now = time.perf_counter() - t0
        batch = []
        while i < len(reqs) and offsets[i] <= now:
            reqs[i].arrival = t0 + offsets[i]
            batch.append(reqs[i])
            i += 1
        if batch:
            done.extend(eng.serve(batch))
        elif i < len(reqs):
            time.sleep(max(offsets[i] - (time.perf_counter() - t0), 0.0))
    return done


def _summary(reqs, wall: float) -> dict:
    lats = np.asarray([r.latency for r in reqs]) * 1e3
    toks = sum(len(r.tokens) for r in reqs)
    return {
        "p50_ms": float(np.percentile(lats, 50)),
        "p99_ms": float(np.percentile(lats, 99)),
        "mean_ms": float(lats.mean()),
        "makespan_s": wall,
        "toks_per_s": toks / wall,
    }


def run(verbose: bool = True) -> List[Row]:
    from repro.configs.registry import ARCHS
    from repro.models import build_model
    from repro.serving.engine import ServingEngine

    cfg = ARCHS["llama3.2-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def fresh_engine():
        eng = ServingEngine(model, params, max_batch=SLOTS, max_len=MAX_LEN,
                            decode_block=DECODE_BLOCK)
        eng.warmup(prompt_lens=list(range(*PROMPT_RANGE)))
        return eng

    # calibrate: serve a probe stream to measure per-request capacity
    probe_eng = fresh_engine()
    probe = _stream(cfg, seed=99)
    t0 = time.perf_counter()
    probe_eng.serve(probe)
    cap = len(probe) / (time.perf_counter() - t0)   # reqs/s at saturation
    rate = UTILIZATION * cap
    offsets = _arrival_offsets(rate, N_REQS, seed=7)

    results = {}
    for mode, drive in (("closed", _drive_closed), ("open", _drive_open)):
        eng = fresh_engine()
        reqs = _stream(cfg, seed=0)
        t0 = time.perf_counter()
        served = drive(eng, reqs, offsets)
        wall = time.perf_counter() - t0
        results[mode] = _summary(served, wall)
        results[mode]["decode_dispatches"] = eng.stats["decode_dispatches"]

    out = {
        "workload": {"n_requests": N_REQS, "slots": SLOTS,
                     "prompt_len": f"{PROMPT_RANGE[0]}..{PROMPT_RANGE[1]-1}",
                     "max_new": f"{MAX_NEW_RANGE[0]}..{MAX_NEW_RANGE[1]-1}",
                     "rate_qps": rate, "arch": cfg.name,
                     "backend": jax.default_backend()},
        "closed_loop": results["closed"],
        "open_loop": results["open"],
        "p50_speedup": results["closed"]["p50_ms"] / results["open"]["p50_ms"],
        "p99_speedup": results["closed"]["p99_ms"] / results["open"]["p99_ms"],
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_e2e_real.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    if verbose:
        for mode in ("closed", "open"):
            r = results[mode]
            print(f"# {mode}: p50 {r['p50_ms']:.1f}ms | "
                  f"p99 {r['p99_ms']:.1f}ms | mean {r['mean_ms']:.1f}ms | "
                  f"{r['toks_per_s']:.0f} tok/s")
        print(f"# open-loop latency: p50 {out['p50_speedup']:.2f}x, "
              f"p99 {out['p99_speedup']:.2f}x lower -> {path}")
    return [
        ("e2e_real_p99_ms_closed", results["closed"]["p99_ms"], "baseline"),
        ("e2e_real_p99_ms_open", results["open"]["p99_ms"],
         f"{out['p99_speedup']:.2f}x"),
        ("e2e_real_tok_s_open", results["open"]["toks_per_s"], ""),
    ]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
