"""Plain float32 reference of a dense decoder in the llama layout, as
published for Phi-3-mini (arXiv:2404.14219):

    h   = x + Attn(RMSNorm(x)),  y = h + MLP(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + s)
    Attn: q, k, v projections, rotary embedding on q and k (the
          rotate-half form: the first and second halves of each head are
          the pair that rotates), causal softmax(q k^T / sqrt(D)) v with
          each query head reading key/value head h // (H / K), output
          projection
    MLP(x) = (silu(x W_gate) * (x W_up)) W_down
    logits = RMSNorm_f(x_L) head^T       (untied head)

No cache, no batching, no kernels: one sequence at a time, the whole
sequence causally, every matmul at ``Precision.HIGHEST``. Departures from
the published models: norm weights act as ``1 + s`` (the same function
with the weight shifted by one), and weights are random.

It imports nothing of the program under test. It takes the weights as
plain arrays (made by ``harness.weights`` from the seed), with int8
projections dequantized here. A control (``CONTROLS``) puts a lower
precision in the reference's place by rewriting each dequantized
projection weight once.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def dequant(w, contract) -> jax.Array:
    """float32 weight from bf16 or ``{"w_q", "scales"}`` int8 storage.
    The contracted axes lead, so the per-output-channel scales broadcast
    over the trailing axes."""
    if isinstance(w, dict):
        assert all(a == i for i, a in enumerate(contract)), contract
        return w["w_q"].astype(jnp.float32) * w["scales"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rmsnorm(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s.astype(jnp.float32))


def rope(x, theta):
    """x: (S, ..., D) at positions 0..S-1, rotate-half convention."""
    S, D = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (D // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w: Dict[str, jax.Array], theta: float, eps: float):
    """One block over one sequence. x: (S, d) float32; ``w`` holds float32
    projections wq (d,K,G,D), wk/wv (d,K,D), wo (K,G,D,d), w_gate/w_up
    (d,f), w_down (f,d) and the norm weights ln1, ln2."""
    S = x.shape[0]
    h = rmsnorm(x, w["ln1"], eps)
    q = jnp.einsum("sd,dkgh->skgh", h, w["wq"], precision=HI)
    k = jnp.einsum("sd,dkh->skh", h, w["wk"], precision=HI)
    v = jnp.einsum("sd,dkh->skh", h, w["wv"], precision=HI)
    q, k = rope(q, theta), rope(k, theta)
    D = q.shape[-1]
    scores = jnp.einsum("skgh,tkh->kgst", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(D))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("kgst,tkh->skgh", p, v, precision=HI)
    x = x + jnp.einsum("skgh,kghd->sd", o, w["wo"], precision=HI)
    h = rmsnorm(x, w["ln2"], eps)
    g = jnp.einsum("sd,df->sf", h, w["w_gate"], precision=HI)
    u = jnp.einsum("sd,df->sf", h, w["w_up"], precision=HI)
    m = jax.nn.silu(g) * u
    return x + jnp.einsum("sf,fd->sd", m, w["w_down"], precision=HI)


def logits(x, ln_f, head, eps):
    """x: (n, d) final hidden states -> (n, V) float32 logits."""
    h = rmsnorm(x, ln_f, eps)
    return jnp.einsum("nd,vd->nv", h, head.astype(jnp.float32), precision=HI)


def embed(tokens, table):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# lower precisions for the control


def int_fake_quant(w, contract, bits: int):
    """Symmetric per-output-channel integer rounding of a float32 weight."""
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(w), axis=contract, keepdims=True)
    s = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


CONTROLS = {
    # weight-only int4 in place of weight-only int8
    "int4": lambda w, contract: int_fake_quant(w, contract, 4),
}
