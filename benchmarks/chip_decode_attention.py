#!/usr/bin/env python3
"""Decode attention over a stacked contiguous KV cache, timed on one chip.

    python benchmarks/chip_decode_attention.py [--reps 20] [--out FILE]

For each served shape (phi3-mini: 32 layers, 8 slots x 512, 32 MHA heads
of 96; Yi-9B's 24-layer stage: 8 slots x 2,048, 4 KV heads of 128 with 8
query heads each; bf16 caches at full size on the device), one decode
step's attention over every layer, as the decode segment's layer scan runs
it, through:

* ``xla``: the layer's slice cut out of the carry and the masked XLA
  attention over all positions;
* ``kernel``: ``stacked_decode_attention``, in blocks of
  ``stacked_block_len``.

Live lengths are drawn per step from the seed: each slot is live with the
cell's measured occupancy and sits at a position drawn around the cell's
mean live position (phi3's chat: 0.16 and ~80; Yi's batch: 0.45 and ~780),
plus one all-live case at the last position. Prints one JSON line per
(shape, case, path) with the median milliseconds per step, and the kernel's
largest gap from XLA over live slots. Needs a TPU: ``stacked_decode_attention``
lowers to Mosaic only there.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from repro.kernels.decode_attention import (  # noqa: E402
    stacked_block_len, stacked_decode_attention, stacked_decode_plan)
from repro.models.layers import attention_core  # noqa: E402

# name: (L, B, S, K, G, D, live share, mean live position)
SHAPES = {
    "phi3-8x512": (32, 8, 512, 32, 1, 96, 0.16, 80),
    "yi-8x2048": (24, 8, 2048, 4, 8, 128, 0.45, 780),
}


def _lengths(rng, B, S, share, mean, n):
    live = rng.random((n, B)) < share
    pos = np.clip(rng.lognormal(np.log(mean), 0.8, (n, B)), 1, S - 1)
    return np.where(live, pos.astype(np.int32) + 1, 0).astype(np.int32)


def _step(path):
    """One decode step's attention over every layer of the carry."""
    def run(q, ks, vs, vlen):
        plan = stacked_decode_plan(vlen, ks)

        def body(acc, layer):
            if path == "xla":
                o = attention_core(
                    q[:, None], lax.dynamic_index_in_dim(ks, layer, 0, False),
                    lax.dynamic_index_in_dim(vs, layer, 0, False),
                    causal=False, kv_valid_len=vlen)[:, 0]
            else:
                o = stacked_decode_attention(q, ks, vs, layer, plan)
            return acc + o.astype(jnp.float32), None
        acc, _ = lax.scan(body, jnp.zeros(q.shape, jnp.float32),
                          jnp.arange(ks.shape[0], dtype=jnp.int32))
        return acc
    return jax.jit(run)


def _time(fn, args_list, reps):
    out = [fn(*a) for a in args_list[:2]]          # compile and warm
    jax.block_until_ready(out)
    times = []
    for r in range(reps):
        a = args_list[r % len(args_list)]
        t = time.perf_counter()
        jax.block_until_ready(fn(*a))
        times.append((time.perf_counter() - t) * 1e3)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1600)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    rows = []
    for name, (L, B, S, K, G, D, share, mean) in SHAPES.items():
        rng = np.random.default_rng(args.seed)
        key = jax.random.PRNGKey(args.seed)
        k1, k2, k3 = jax.random.split(key, 3)
        ks = jax.random.normal(k1, (L, B, S, K, D), jnp.bfloat16)
        vs = jax.random.normal(k2, (L, B, S, K, D), jnp.bfloat16)
        q = jax.random.normal(k3, (B, K, G, D), jnp.bfloat16)
        cases = {"cell": _lengths(rng, B, S, share, mean, 16),
                 "all-full": np.full((1, B), S, np.int32)}
        block = stacked_block_len(S, K, D, 2)
        for case, vls in cases.items():
            args_list = [(q, ks, vs, jnp.asarray(v)) for v in vls]
            ref = _step("xla")
            want = [np.asarray(ref(*a)) for a in args_list]
            for path in ("xla", "kernel"):
                row = {"shape": name, "case": case, "path": path,
                       "block": block if path == "kernel" else None,
                       "live_share": float((vls > 0).mean()),
                       "mean_live_len": float(vls[vls > 0].mean())}
                try:
                    fn = ref if path == "xla" else _step(path)
                    row["max_gap_vs_xla"] = max(
                        float(np.abs(np.asarray(fn(*a)) - w)[v > 0].max(
                            initial=0.0))
                        for a, w, v in zip(args_list, want, vls))
                    row["ms_per_step"] = round(
                        _time(fn, args_list, args.reps), 4)
                except Exception as e:  # noqa: BLE001 - e.g. VMEM refused
                    row["error"] = f"{type(e).__name__}: {e}"[:300]
                rows.append(row)
                print(json.dumps(row), flush=True)
        del ks, vs
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
