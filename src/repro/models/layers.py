"""Shared model layers: norms, RoPE, GQA attention, SwiGLU, embeddings.

All layers are pure functions over explicit parameter pytrees so that
``jax.eval_shape`` can build full-size configs with zero allocation (dry-run)
and so the profiler can AOT-compile arbitrary variants.

Attention uses the grouped layout throughout: q is (B, S, K, G, D) where
K = n_kv_heads and G = q_per_kv; k/v are (B, T, K, D). This keeps GQA exact
without materializing repeated KV (which would inflate the decode-cache
memory term by G).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# initializers


def dense_init(rng, shape, dtype, in_axis: int = -2) -> jax.Array:
    """LeCun-normal style init, fan-in along ``in_axis``."""
    fan_in = shape[in_axis]
    scale = 1.0 / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


def embed_init(rng, shape, dtype) -> jax.Array:
    return (jax.random.normal(rng, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def rmsnorm_init(d: int, dtype) -> jax.Array:
    return jnp.zeros((d,), dtype)  # scale stored as (1 + s)


# ---------------------------------------------------------------------------
# rotary position embeddings


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim//2,)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, K?, G?, D) with positions (..., S) broadcastable over heads."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    # broadcast angles over any head dims between S and D
    for _ in range(x.ndim - angles.ndim):
        angles = angles[..., None, :]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention


def constrain_residual(x: jax.Array) -> jax.Array:
    """Sequence-parallel sharding constraint on the (B, S, d) residual
    stream (no-op outside a mesh/dry-run context). Keeps the remat-saved
    carries sharded over the model axis."""
    from repro.distributed.parallel import get_activation_sharding
    ctx = get_activation_sharding()
    if ctx is None or x.ndim != 3:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(ctx.batch(x.shape[0]), ctx.seq(x.shape[1]), None)
    return jax.lax.with_sharding_constraint(x, spec)


def _chunk_mask(causal, qi, kj, qb, kb, q_offset):
    t_idx = kj * kb + lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    if not causal:
        return jnp.ones((qb, kb), bool)
    s_idx = qi * qb + q_offset + lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
    return t_idx <= s_idx


def _chunked_fwd(q, k, v, causal, q_offset, qb, kb):
    """Returns (out (B,S,K,G,D), lse (nq,B,K,G,qb))."""
    B, S, K, G, D = q.shape
    T = k.shape[1]
    nq, nk = S // qb, T // kb
    scale = D ** -0.5
    qr = q.reshape(B, nq, qb, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(B, nk, kb, K, D).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nk, kb, K, D).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_blk):
        qi, qblk = qi_blk                      # qblk: (B, qb, K, G, D)

        def kv_step(carry, kj_blk):
            m_run, l_run, acc = carry
            kj, kblk, vblk = kj_blk
            s = jnp.einsum("bskgd,btkd->bkgst", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(_chunk_mask(causal, qi, kj, qb, kb, q_offset),
                          s, -1e30)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_run, m_cur)
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkd->bkgsd", p.astype(v.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, K, G, qb), -1e30, jnp.float32)
        l0 = jnp.zeros((B, K, G, qb), jnp.float32)
        a0 = jnp.zeros((B, K, G, qb, D), jnp.float32)
        (m, lsum, acc), _ = lax.scan(kv_step, (m0, l0, a0),
                                  (jnp.arange(nk), kr, vr))
        lsum = jnp.maximum(lsum, 1e-30)
        out = acc / lsum[..., None]
        lse = m + jnp.log(lsum)                # (B, K, G, qb)
        return None, (out.astype(q.dtype), lse)

    _, (outs, lses) = lax.scan(q_step, None, (jnp.arange(nq), qr))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, K, G, D)
    return out, lses


def _chunked_bwd(q, k, v, out, lse, dout, causal, q_offset, qb, kb):
    """FlashAttention-style recomputing backward: nothing S x T is stored."""
    B, S, K, G, D = q.shape
    T = k.shape[1]
    nq, nk = S // qb, T // kb
    scale = D ** -0.5
    qr = q.reshape(B, nq, qb, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    kr = k.reshape(B, nk, kb, K, D).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nk, kb, K, D).transpose(1, 0, 2, 3, 4)
    do_r = dout.reshape(B, nq, qb, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    o_r = out.reshape(B, nq, qb, K, G, D).transpose(1, 0, 2, 3, 4, 5)
    # D_i = rowsum(dOut * Out): (nq, B, K, G, qb)
    delta = jnp.einsum("nbskgd,nbskgd->nbkgs", do_r.astype(jnp.float32),
                       o_r.astype(jnp.float32))

    def kv_step(_, kj_blk):
        kj, kblk, vblk = kj_blk

        def q_step(carry, qi_blk):
            dk_acc, dv_acc = carry
            qi, qblk, doblk, lse_i, delta_i = qi_blk
            s = jnp.einsum("bskgd,btkd->bkgst", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = _chunk_mask(causal, qi, kj, qb, kb, q_offset)
            s = jnp.where(mask, s, -1e30)
            p = jnp.exp(s - lse_i[..., None])            # (B,K,G,qb,kb)
            dp = jnp.einsum("bskgd,btkd->bkgst",
                            doblk.astype(jnp.float32),
                            vblk.astype(jnp.float32))
            ds = p * (dp - delta_i[..., None])
            dq_i = jnp.einsum("bkgst,btkd->bskgd", ds,
                              kblk.astype(jnp.float32)) * scale
            dk_acc = dk_acc + jnp.einsum(
                "bkgst,bskgd->btkd", ds,
                qblk.astype(jnp.float32)) * scale
            dv_acc = dv_acc + jnp.einsum(
                "bkgst,bskgd->btkd", p, doblk.astype(jnp.float32))
            return (dk_acc, dv_acc), dq_i

        dk0 = jnp.zeros((B, kb, K, D), jnp.float32)
        dv0 = jnp.zeros((B, kb, K, D), jnp.float32)
        (dk_j, dv_j), dq_parts = lax.scan(
            q_step, (dk0, dv0), (jnp.arange(nq), qr, do_r, lse, delta))
        return None, (dk_j, dv_j, dq_parts)

    _, (dks, dvs, dq_parts) = lax.scan(kv_step, None,
                                       (jnp.arange(nk), kr, vr))
    # dq_parts: (nk, nq, B, qb, K, G, D) -> sum over kv blocks
    dq = jnp.sum(dq_parts, axis=0).transpose(1, 0, 2, 3, 4, 5) \
        .reshape(B, S, K, G, D).astype(q.dtype)
    dk = dks.transpose(1, 0, 2, 3, 4).reshape(B, T, K, D).astype(k.dtype)
    dv = dvs.transpose(1, 0, 2, 3, 4).reshape(B, T, K, D).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_chunked_cvjp(q, k, v, causal, q_offset, qb, kb):
    out, _ = _chunked_fwd(q, k, v, causal, q_offset, qb, kb)
    return out


def _attention_chunked_cvjp_fwd(q, k, v, causal, q_offset, qb, kb):
    out, lse = _chunked_fwd(q, k, v, causal, q_offset, qb, kb)
    return out, (q, k, v, out, lse)


def _attention_chunked_cvjp_bwd(causal, q_offset, qb, kb, res, dout):
    q, k, v, out, lse = res
    return _chunked_bwd(q, k, v, out, lse, dout, causal, q_offset, qb, kb)


_attention_chunked_cvjp.defvjp(_attention_chunked_cvjp_fwd,
                               _attention_chunked_cvjp_bwd)


def _attention_chunked(q, k, v, *, causal, q_offset, kv_valid_len,
                       q_block: int = 256, k_block: int = 512) -> jax.Array:
    """Flash-style online-softmax attention in pure XLA with a recomputing
    custom-vjp backward: the S x T score matrix never materializes in either
    pass. Used for long-sequence lowering when the Pallas kernel can't
    target the backend (the dry-run path). kv_valid_len is not supported
    here (callers fall back to the plain path)."""
    assert kv_valid_len is None
    B, S, K, G, D = q.shape
    T = k.shape[1]
    qb = min(q_block, S)
    kb = min(k_block, T)
    assert S % qb == 0 and T % kb == 0, (S, T, qb, kb)
    return _attention_chunked_cvjp(q, k, v, causal, int(q_offset), qb, kb)


def decode_attention_splitk(q, kc, vc, valid_len, ctx) -> jax.Array:
    """Flash-decode over a sequence-sharded KV cache via shard_map.

    q: (B, 1, K, G, D) replicated over the model axis; kc/vc: (B, T, K, D)
    sharded T over the model axis. Each shard computes a local
    online-softmax partial (m, l, acc); the combine is three tiny psums of
    (B,K,G,{1,D}) — no score or cache all-gather (§Perf A-iter2).
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    B, _, K, G, D = q.shape
    T = kc.shape[1]
    m_axis = ctx.model_axis
    T_local = T // ctx.model_size
    dax = ctx.batch(B)
    q_spec = P(dax, None, None, None, None)
    kv_spec = P(dax, m_axis, None, None)
    scalar = P()

    def local_fn(q_l, k_l, v_l, vlen):
        # q_l: (B_l, 1, K, G, D); k_l/v_l: (B_l, T_local, K, D)
        offset = jax.lax.axis_index(m_axis) * T_local
        s = jnp.einsum("bskgd,btkd->bkgst", q_l, k_l,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        t_idx = offset + jnp.arange(T_local)
        s = jnp.where(t_idx[None, None, None, None, :] < vlen, s, -1e30)
        m_loc = jnp.max(s, axis=-1, keepdims=True)          # (b,k,g,1,1)
        m_glob = jax.lax.pmax(m_loc, m_axis)
        p = jnp.exp(s - m_glob)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bkgst,btkd->bskgd", p.astype(v_l.dtype), v_l,
                         preferred_element_type=jnp.float32)
        l_glob = jax.lax.psum(l_loc, m_axis)                # (b,k,g,1,1)
        acc = jax.lax.psum(acc, m_axis)                     # (b,1,k,g,D)
        out = acc / jnp.maximum(l_glob[:, :, :, :, 0], 1e-30
                                ).transpose(0, 3, 1, 2)[..., None]
        return out.astype(q_l.dtype)

    fn = shard_map(local_fn, mesh=ctx.mesh,
                   in_specs=(q_spec, kv_spec, kv_spec, scalar),
                   out_specs=q_spec, check_rep=False)
    return fn(q, kc, vc, jnp.asarray(valid_len, jnp.int32))


# The dense XLA attention holds float32 (B, K, G, S, T) scores and their
# softmax at once. Past this many bytes of scores (8 rows of 1,024 with 32
# heads) a causal self-attention streams them in blocks instead: 8 rows of
# a 2,048 prefill bucket would hold 4 GiB, which does not fit beside a 9 GB
# model on a 16 GB chip.
DENSE_SCORES_MAX_BYTES = 1 << 30


def _sharded() -> bool:
    from repro.distributed.parallel import get_activation_sharding
    ctx = get_activation_sharding()
    return ctx is not None and ctx.mesh is not None


def _stacked_route(impl: str, kernel, fallback, *args):
    """Where work over the stacked contiguous decode carry runs (the rule
    ``attention_core`` documents): ``kernel(*args, interpret=...)`` where
    the program is lowered for a TPU, or in interpret mode under
    ``impl="pallas_interpret"``; ``fallback(*args)`` on other platforms and
    under an activation-sharding mesh (the kernel reads one device's whole
    cache)."""
    if _sharded():
        return fallback(*args)
    if impl == "pallas_interpret":
        return kernel(*args, interpret=True)
    return lax.platform_dependent(*args, tpu=kernel, default=fallback)


def stacked_decode_reads(plan, k_stack: Any, impl: str) -> jax.Array:
    """KV positions one decode step's attention over the stacked carry
    ``k_stack`` (L, B, S, K, D) reads, summed over slots, where
    ``attention_core`` routes it under ``plan`` (its ``layer`` case): the
    kernel's live blocks, else all S positions of all B slots."""
    _L, B, S = k_stack.shape[:3]
    return _stacked_route(
        impl, lambda plan, interpret=False: plan.positions_read,
        lambda plan: jnp.int32(B * S), plan)


def attention_core(
    q: jax.Array,                 # (B, S, K, G, D)
    k: jax.Array,                 # (B, T, K, D)
    v: jax.Array,                 # (B, T, K, D)
    *,
    causal: bool,
    q_offset: Any = 0,            # query position offset (decode: cache_len)
    kv_valid_len: Optional[Any] = None,   # mask kv positions >= this;
                                          # scalar, or (B,) per-sequence
    impl: str = "xla",
    layer: Optional[Any] = None,  # k/v are a stacked (L, B, T, K, D)
                                  # carry; attend over this layer of it,
                                  # kv_valid_len then a StackedPlan
) -> jax.Array:
    """Grouped-query attention. Returns (B, S, K, G, D).

    Routing, by what the call shows:

    * Decode over the stacked contiguous carry (``layer`` given, one query
      token, ``kv_valid_len`` the step's ``stacked_decode_plan`` of the
      per-slot lengths, built once outside the layer scan; what it reads:
      ``stacked_decode_reads``) runs the
      length-aware Pallas kernel
      (``kernels.decode_attention.stacked_decode_attention``) where the
      program is lowered for a TPU, and in interpret mode under
      ``impl="pallas_interpret"`` anywhere: each slot reads only its KV
      blocks below its valid length, and a slot of length 0 reads nothing
      (its output is zeros). On other platforms, or under an
      activation-sharding mesh, the layer's slice is cut out and the rules
      below apply to it, with the plan's per-slot lengths.
    * Other per-slot valid lengths lower through the plain XLA path; the
      Pallas and split-K kernels take a single scalar length.
    * Otherwise ``impl`` chooses prefill's path: ``"xla"``, ``"xla_chunked"``
      or a Pallas flash kernel; a causal self-attention whose dense float32
      scores would pass ``DENSE_SCORES_MAX_BYTES`` streams them in blocks
      whatever the impl string.
    """
    if layer is not None:
        assert q.shape[1] == 1, "the stacked carry takes one query token"

        def sliced(q, k, v, layer, plan):
            return attention_core(
                q, lax.dynamic_index_in_dim(k, layer, keepdims=False),
                lax.dynamic_index_in_dim(v, layer, keepdims=False),
                causal=causal, q_offset=q_offset,
                kv_valid_len=plan.valid_len, impl=impl)

        def kernel(q, k, v, layer, plan, interpret=False):
            from repro.kernels.decode_attention import \
                stacked_decode_attention
            return stacked_decode_attention(q[:, 0], k, v, layer, plan,
                                            interpret=interpret)[:, None]

        return _stacked_route(impl, kernel, sliced, q, k, v, layer,
                              kv_valid_len)
    vec_valid = kv_valid_len is not None and jnp.ndim(kv_valid_len) > 0
    if vec_valid:
        impl = "xla"
    if impl == "xla_chunked" and q.shape[1] == 1 and kv_valid_len is not None:
        # decode against a long cache: use the split-K shard_map path when
        # the cache is sequence-sharded over the model axis
        from repro.distributed.parallel import get_activation_sharding
        ctx = get_activation_sharding()
        if ctx is not None and ctx.mesh is not None \
                and k.shape[1] > 1 and k.shape[1] % ctx.model_size == 0 \
                and k.shape[2] % ctx.model_size != 0:
            # (KV-head-sharded caches keep the GSPMD path: resharding the
            # cache into the split-K layout would cost an all-to-all)
            return decode_attention_splitk(q, k, v, kv_valid_len, ctx)
    if impl.startswith("pallas"):
        from repro.kernels import ops as kops
        return kops.flash_attention_grouped(
            q, k, v, causal=causal, q_offset=q_offset,
            kv_valid_len=kv_valid_len,
            interpret=impl == "pallas_interpret")
    # Causal self-attention whose dense float32 scores would pass
    # DENSE_SCORES_MAX_BYTES takes the chunked path too, whatever the impl
    # string.
    if impl in ("xla", "xla_chunked") and kv_valid_len is None \
            and (impl == "xla_chunked" or causal and 4 * k.shape[1]
                 * math.prod(q.shape[:-1]) > DENSE_SCORES_MAX_BYTES) \
            and q.shape[1] > 256 and q.shape[1] % 256 == 0 \
            and k.shape[1] % 512 == 0:
        return _attention_chunked(q, k, v, causal=causal, q_offset=q_offset,
                                  kv_valid_len=None)
    B, S, K, G, D = q.shape
    T = k.shape[1]
    scale = D ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or kv_valid_len is not None:
        t_idx = jnp.arange(T)
        mask = jnp.ones((S, T), bool)
        if causal:
            s_idx = jnp.arange(S)[:, None] + q_offset
            mask = t_idx[None, :] <= s_idx
        if kv_valid_len is not None and not vec_valid:
            mask = mask & (t_idx[None, :] < kv_valid_len)
        if vec_valid:
            # (B, 1, 1, S, T) mask broadcasting over scores (B, K, G, S, T)
            per_seq = t_idx[None, :] < jnp.reshape(kv_valid_len, (-1, 1))
            mask = mask[None] & per_seq[:, None, :]
            mask = mask[:, None, None]
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def window_attention_core(
    q: jax.Array,                 # (B, S, K, G, D) S-token decode window
    k: jax.Array,                 # (B, T, K, D) full cache (window written)
    v: jax.Array,                 # (B, T, K, D)
    pos: jax.Array,               # (B,) position of the window's first token
) -> jax.Array:
    """Attention for a teacher-forced decode window (speculative verify).

    Window token ``s`` of slot ``b`` sits at absolute position
    ``pos[b] + s`` and may attend to cache positions ``<= pos[b] + s``
    (itself included — its KV rows are already written). This is the
    batched generalization of the decode step's ``kv_valid_len = pos+1``
    masked attend: at ``S == 1`` the mask reduces to exactly that, so a
    verify window of one token is bit-identical to a plain decode step.
    """
    B, S, K, G, D = q.shape
    T = k.shape[1]
    scale = D ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    t_idx = jnp.arange(T)[None, None, :]                    # (1, 1, T)
    s_pos = pos[:, None, None] + jnp.arange(S)[None, :, None]  # (B, S, 1)
    mask = (t_idx <= s_pos)[:, None, None]      # (B, 1, 1, S, T)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkd->bskgd", probs, v)


def paged_attention_core(
    q: jax.Array,                 # (B, 1, K, G, D) one decode token per slot
    k_pool: jax.Array,            # (n_pages, page_size, K, D) shared pool
    v_pool: jax.Array,
    block_table: jax.Array,       # (B, P) page ids, sentinel = n_pages
    *,
    kv_valid_len: Any,            # scalar or (B,) per-slot valid lengths
    impl: str = "xla",
) -> jax.Array:
    """Decode attention over a paged KV cache (vLLM-style block tables).

    On the Pallas path the kernel walks the block table directly (HBM
    traffic is one pass over the *live* pages); the XLA path materializes
    the slot's logical view with a page gather and reuses the standard
    masked ``attention_core``, which keeps outputs bit-identical to the
    contiguous layout (P * page_size == S_max, and positions beyond
    ``kv_valid_len`` mask to exact zeros either way).
    """
    from repro.models import kvcache as KV
    if impl.startswith("pallas") and q.shape[1] == 1:
        from repro.kernels.decode_attention import paged_decode_attention
        out = paged_decode_attention(
            q[:, 0], k_pool, v_pool, block_table, kv_valid_len,
            interpret=impl == "pallas_interpret")
        return out[:, None]
    kc = KV.gather_block_kv(k_pool, block_table)
    vc = KV.gather_block_kv(v_pool, block_table)
    return attention_core(q, kc, vc, causal=False,
                          kv_valid_len=kv_valid_len, impl="xla")


def paged_update_attend(
    q: jax.Array,                 # (B, 1, K, G, D) one decode token per slot
    k: jax.Array,                 # (B, 1, K, D) the token's fresh k/v rows
    v: jax.Array,
    k_pool: jax.Array,            # (n_phys, page_size, K, D) shared pool
    v_pool: jax.Array,
    block_table: jax.Array,       # (B, P) page ids, sentinel = n_phys - 1
    pos: Any,                     # scalar or (B,) write position per slot
    *,
    impl: str = "xla",
) -> tuple:
    """One decode step's paged KV write + attend; returns (o, k_pool,
    v_pool).

    On the Pallas path both halves run in one fused kernel
    (``fused_paged_decode_attention``): the new row is injected into the
    write page's VMEM tile before the scores see it and the page flushes
    back through an aliased output, so the decode loop carries no
    separate XLA pool scatter. This requires the engine's pallas-paged
    pool layout (one trash page at the sentinel index, written pages
    private to their slot — see the kernel's docstring). The XLA path
    keeps the two-op form (scatter with sentinel drop, then the gathered
    masked attend), which is bit-identical to the contiguous layout.
    """
    from repro.models import kvcache as KV
    if impl.startswith("pallas") and q.shape[1] == 1:
        from repro.kernels.decode_attention import \
            fused_paged_decode_attention
        o, k_pool, v_pool = fused_paged_decode_attention(
            q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, block_table, pos,
            interpret=impl == "pallas_interpret")
        return o[:, None], k_pool, v_pool
    k_pool, v_pool = KV.paged_update_layer_cache(
        k_pool, v_pool, k, v, block_table, pos)
    o = paged_attention_core(q, k_pool, v_pool, block_table,
                             kv_valid_len=pos + 1, impl=impl)
    return o, k_pool, v_pool


def attn_params_init(rng, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                     dtype) -> Params:
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], (d_model, n_kv, n_heads // n_kv, head_dim),
                         dtype, in_axis=0),
        "wk": dense_init(ks[1], (d_model, n_kv, head_dim), dtype, in_axis=0),
        "wv": dense_init(ks[2], (d_model, n_kv, head_dim), dtype, in_axis=0),
        "wo": dense_init(ks[3], (n_kv, n_heads // n_kv, head_dim, d_model),
                         dtype, in_axis=0),
    }


def attn_qkv(x: jax.Array, p: Params,
             qimpl: str = "xla") -> Tuple[jax.Array, jax.Array, jax.Array]:
    from repro.models.quantize import QuantizedWeight, qeinsum
    if isinstance(p["wq"], QuantizedWeight):
        # per-layer slices: wq (d,k,g,h) / wk,wv (d,k,h) contract axis 0
        q = qeinsum("bsd,dkgh->bskgh", x, p["wq"], (0,), impl=qimpl)
        k = qeinsum("bsd,dkh->bskh", x, p["wk"], (0,), impl=qimpl)
        v = qeinsum("bsd,dkh->bskh", x, p["wv"], (0,), impl=qimpl)
        return q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    return q, k, v


def attn_out(o: jax.Array, p: Params, qimpl: str = "xla") -> jax.Array:
    from repro.models.quantize import QuantizedWeight, qeinsum
    if isinstance(p["wo"], QuantizedWeight):
        # per-layer slice: wo (k,g,h,d) contracts its leading head dims
        return qeinsum("bskgh,kghd->bsd", o, p["wo"], (0, 1, 2),
                       impl=qimpl).astype(o.dtype)
    return jnp.einsum("bskgh,kghd->bsd", o, p["wo"])


def self_attention(
    x: jax.Array, p: Params, cfg_theta: float, *,
    causal: bool = True,
    positions: Optional[jax.Array] = None,
    impl: str = "xla",
    rope: bool = True,
) -> jax.Array:
    B, S, _ = x.shape
    q, k, v = attn_qkv(x, p)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if rope:
        q = apply_rope(q, positions, cfg_theta)
        k = apply_rope(k, positions, cfg_theta)
    o = attention_core(q, k, v, causal=causal, impl=impl)
    return attn_out(o, p)


# ---------------------------------------------------------------------------
# SwiGLU MLP


def mlp_params_init(rng, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype, in_axis=0),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype, in_axis=0),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype, in_axis=0),
    }


def swiglu(x: jax.Array, p: Params, qimpl: str = "xla") -> jax.Array:
    from repro.models.quantize import QuantizedWeight, qeinsum
    if isinstance(p["w_gate"], QuantizedWeight):
        g = qeinsum("bsd,df->bsf", x, p["w_gate"], (0,), impl=qimpl)
        u = qeinsum("bsd,df->bsf", x, p["w_up"], (0,), impl=qimpl)
        h = jax.nn.silu(g) * u.astype(g.dtype)
        return qeinsum("bsf,fd->bsd", h, p["w_down"], (0,),
                       impl=qimpl).astype(x.dtype)
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g) * u
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings / lm head


def embed_tokens(tokens: jax.Array, table: jax.Array, dtype) -> jax.Array:
    return jnp.take(table, tokens, axis=0).astype(dtype)


def select_last(x: jax.Array, length: Optional[jax.Array]) -> jax.Array:
    """Select the last *valid* position per sequence: x (B, S, d) -> (B, 1, d).

    ``length`` is an optional (B,) int array of valid prefix lengths (prompts
    right-padded to a shared bucket); None means the full sequence is valid,
    which reduces to ``x[:, -1:]``. Used by prefill so the engine reads the
    next-token logits at position length-1 rather than at the padded end.
    """
    if length is None:
        return x[:, -1:]
    idx = jnp.clip(length.astype(jnp.int32) - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def lm_logits(x: jax.Array, head: jax.Array) -> jax.Array:
    # head: (vocab, d_model); logits in f32 for a stable softmax/xent
    return jnp.einsum("bsd,vd->bsv", x, head,
                      preferred_element_type=jnp.float32)


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean token cross-entropy; logits (B,S,V) f32, targets (B,S) int.

    The gold logit is extracted with a mask-reduce rather than
    take_along_axis: a gather along a vocab-sharded axis makes GSPMD
    replicate the full logits; the mask-reduce stays sharded (partial sum +
    small all-reduce)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    mask = vocab_iota == targets[..., None]
    gold = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
    return jnp.mean(logz - gold)
