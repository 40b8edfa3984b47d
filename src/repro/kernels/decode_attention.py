"""Flash-decode (split-K) GQA attention for one-token serving, Pallas TPU.

One query token attends to a long KV cache. Grid = (B, K, n_t_blocks): the KV
sequence is tiled; each step computes a partial online-softmax update for all
G query heads sharing the KV head, with running (m, l, acc) in VMEM scratch.
This is the TPU analogue of FlashDecoding's split-K: HBM traffic is exactly
one pass over the KV cache, the dominant term for decode at 32k-524k context.

The G dimension (q heads per KV head) rides inside the block as the row dim
of a (G, block_t) score matrix, so the MXU sees (G x D) @ (D x block_t).

``paged_decode_attention`` is the block-table variant for the paged KV
layout (``repro.models.kvcache``): the KV tile for grid step ``j`` of slot
``b`` is page ``block_table[b, j]`` of a shared (n_pages, page_size, K, D)
pool, resolved in the BlockSpec index map from a scalar-prefetched block
table — the page indirection costs no extra HBM pass, and per-slot valid
lengths ride in a second prefetched scalar.

``fused_paged_decode_attention`` additionally folds the token's KV *write*
into the same kernel: the new k/v row is injected into the write page's
tile in VMEM before the scores are computed, and the updated page is
flushed back through an aliased pool output — the separate per-step XLA
pool scatter (and its read-modify-write pass over the pool) disappears
from the decode loop. The pool output's BlockSpec pins every grid step of
a (slot, head) pair to that slot's single write page, so exactly one
store (at the write page's logical block) defines the flushed content.
Safety relies on two invariants the serving engine maintains: a written
page is private to its slot (copy-on-write guarantees refcount 1), and
the pool carries one extra *trash page* at index ``n_pages - 1`` — equal
to the block table's sentinel value — so writes by inactive slots land
harmlessly in a page no block table references for live reads (stale
trash contents sit behind ``valid_len`` and mask to exact zeros).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 512
NEG_INF = -1e30


def _decode_kernel(vlen_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   block_t: int, n_t_blocks: int, sm_scale: float):
    tj = pl.program_id(2)

    @pl.when(tj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    t_start = tj * block_t
    valid_len = vlen_ref[0]

    @pl.when(t_start < valid_len)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bt, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bt, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        t_idx = t_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t_idx < valid_len, s, NEG_INF)  # (G, bt)
        m_prev = m_scr[...]                          # (G, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(tj == n_t_blocks - 1)
    def _finish():
        lsum = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / lsum).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid_len: Optional[jax.Array] = None, *,
                     block_t: int = DEFAULT_BLOCK_T,
                     interpret: bool = False) -> jax.Array:
    """q: (B, K, G, D); k/v: (B, K, T, D); valid_len scalar (<= T).

    Returns (B, K, G, D).
    """
    B, K, G, D = q.shape
    T = k.shape[2]
    block_t = min(block_t, T)
    assert T % block_t == 0, (T, block_t)
    nt = T // block_t
    if valid_len is None:
        valid_len = jnp.array([T], jnp.int32)
    else:
        valid_len = jnp.asarray(valid_len, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, block_t=block_t,
                               n_t_blocks=nt, sm_scale=D ** -0.5)
    grid = (B, K, nt)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, j, vlen: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_t, D),
                             lambda b, h, j, vlen: (b, h, j, 0)),
                pl.BlockSpec((1, 1, block_t, D),
                             lambda b, h, j, vlen: (b, h, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, j, vlen: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(valid_len, q, k, v)


def _online_softmax_step(q, k, v, t_start, valid_len, m_scr, l_scr,
                         acc_scr, h, sm_scale):
    """Fold one (ps, D) KV tile into head ``h``'s running (m, l, acc)."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
    t_idx = t_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_idx < valid_len, s, NEG_INF)      # (G, ps)
    m_prev = m_scr[h]                                 # (G, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[h] = m_new


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _finish_heads(o_ref, l_scr, acc_scr):
    lsum = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / lsum).astype(o_ref.dtype)


def _paged_scratch(K: int, G: int, D: int):
    return [pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32)]


# The paged kernels block a whole page, all K heads: (1, page_size, K, D).
# A one-head block (1, page_size, 1, D) would put a block of 1 on the K
# axis, second-to-last in the pool, which Mosaic refuses unless K == 1 (the
# last two block dims must tile (8, 128) or span the array). Heads are
# iterated inside the kernel instead, one MXU pass each, so every page is
# fetched once per slot in a single DMA.


def _paged_decode_kernel(vlen_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *,
                         page_size: int, n_t_blocks: int, sm_scale: float):
    b = pl.program_id(0)
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    t_start = tj * page_size
    valid_len = vlen_ref[b]

    @pl.when(t_start < valid_len)
    def _step():
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h].astype(jnp.float32)           # (G, D)
            k = k_ref[0, :, h].astype(jnp.float32)        # (ps, D)
            v = v_ref[0, :, h].astype(jnp.float32)        # (ps, D)
            _online_softmax_step(q, k, v, t_start, valid_len,
                                 m_scr, l_scr, acc_scr, h, sm_scale)

    @pl.when(tj == n_t_blocks - 1)
    def _finish():
        _finish_heads(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           valid_len: Optional[jax.Array] = None, *,
                           interpret: bool = False) -> jax.Array:
    """Flash-decode through a block table over a shared page pool.

    q: (B, K, G, D); k_pool/v_pool: (n_pages, page_size, K, D) — the paged
    cache layout of ``repro.models.kvcache``; block_table: (B, P) page ids
    (sentinel entries >= n_pages clamp to the last page and are masked by
    ``valid_len``); valid_len: scalar or (B,) per-slot valid lengths over
    the slot's *logical* sequence of P * page_size positions.

    Returns (B, K, G, D).
    """
    B, K, G, D = q.shape
    n_pages, page_size = k_pool.shape[:2]
    P = block_table.shape[1]
    if valid_len is None:
        valid_len = jnp.full((B,), P * page_size, jnp.int32)
    else:
        valid_len = jnp.broadcast_to(
            jnp.asarray(valid_len, jnp.int32), (B,))
    bt = jnp.clip(block_table.astype(jnp.int32), 0, n_pages - 1)

    kernel = functools.partial(_paged_decode_kernel, page_size=page_size,
                               n_t_blocks=P, sm_scale=D ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, K, G, D),
                             lambda b, j, vlen, bt: (b, 0, 0, 0)),
                pl.BlockSpec((1, page_size, K, D),
                             lambda b, j, vlen, bt: (bt[b, j], 0, 0, 0)),
                pl.BlockSpec((1, page_size, K, D),
                             lambda b, j, vlen, bt: (bt[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, K, G, D),
                                   lambda b, j, vlen, bt: (b, 0, 0, 0)),
            scratch_shapes=_paged_scratch(K, G, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(valid_len, bt, q, k_pool, v_pool)


def _fused_paged_decode_kernel(vlen_ref, wblk_ref, woff_ref, bt_ref,
                               q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
                               o_ref, ko_ref, vo_ref,
                               m_scr, l_scr, acc_scr, *,
                               page_size: int, n_t_blocks: int,
                               sm_scale: float):
    b = pl.program_id(0)
    tj = pl.program_id(1)

    @pl.when(tj == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    t_start = tj * page_size
    valid_len = vlen_ref[b]
    is_w = tj == wblk_ref[b]
    # Inject the new token's k/v row into this tile when it is the write
    # block, then attend over the *updated* tile: the row is visible to
    # the very score pass that needs it (valid_len == pos + 1 covers it)
    # without ever round-tripping HBM.
    sel = (lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
           == woff_ref[b]) & is_w

    @pl.when(is_w)
    def _flush():
        # the pool outputs' index maps pin every j of this slot to the
        # write page, so this single store is what the one flush carries
        sel3 = sel[:, :, None]
        ko_ref[0] = jnp.where(sel3, kn_ref[0].astype(jnp.float32),
                              kp_ref[0].astype(jnp.float32)
                              ).astype(ko_ref.dtype)
        vo_ref[0] = jnp.where(sel3, vn_ref[0].astype(jnp.float32),
                              vp_ref[0].astype(jnp.float32)
                              ).astype(vo_ref.dtype)

    @pl.when(t_start < valid_len)
    def _step():
        for h in range(q_ref.shape[1]):
            k = jnp.where(sel, kn_ref[0, 0, pl.ds(h, 1)].astype(jnp.float32),
                          kp_ref[0, :, h].astype(jnp.float32))  # (ps, D)
            v = jnp.where(sel, vn_ref[0, 0, pl.ds(h, 1)].astype(jnp.float32),
                          vp_ref[0, :, h].astype(jnp.float32))
            q = q_ref[0, h].astype(jnp.float32)           # (G, D)
            _online_softmax_step(q, k, v, t_start, valid_len,
                                 m_scr, l_scr, acc_scr, h, sm_scale)

    @pl.when(tj == n_t_blocks - 1)
    def _finish():
        _finish_heads(o_ref, l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_paged_decode_attention(q: jax.Array, k_new: jax.Array,
                                 v_new: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, block_table: jax.Array,
                                 pos: jax.Array, *, interpret: bool = False):
    """One-token paged decode with the KV write fused into the kernel.

    q: (B, K, G, D); k_new/v_new: (B, K, D) — the token's fresh k/v rows;
    k_pool/v_pool: (n_phys, page_size, K, D); block_table: (B, P);
    pos: scalar or (B,) — the position being written (and attended up to,
    inclusive: valid length is ``pos + 1``).

    **Pool contract** (the serving engine's pallas-paged layout): the pool
    carries one trash page at the top, ``n_phys == sentinel + 1`` with
    every sentinel block-table entry equal to ``n_phys - 1``, so inactive
    slots' writes land in the trash page instead of needing per-slot
    write suppression; and a written page is referenced by exactly one
    slot (the engine copies shared pages on write).

    Returns ``(out, k_pool', v_pool')`` with ``out``: (B, K, G, D); the
    pools are updated in place (aliased).
    """
    B, K, G, D = q.shape
    n_phys, page_size = k_pool.shape[:2]
    P = block_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    vlen = pos + 1
    wblk = jnp.clip(pos // page_size, 0, P - 1)
    woff = pos % page_size
    bt = jnp.clip(block_table.astype(jnp.int32), 0, n_phys - 1)
    kn = k_new.reshape(B, 1, K, D)
    vn = v_new.reshape(B, 1, K, D)

    kernel = functools.partial(_fused_paged_decode_kernel,
                               page_size=page_size, n_t_blocks=P,
                               sm_scale=D ** -0.5)
    out_shapes = (
        jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
        jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
    )
    page = pl.BlockSpec((1, page_size, K, D),
                        lambda b, j, vl, wb, wo, bt: (bt[b, j], 0, 0, 0))
    write_page = pl.BlockSpec((1, page_size, K, D),
                              lambda b, j, vl, wb, wo, bt:
                              (bt[b, wb[b]], 0, 0, 0))
    per_slot = pl.BlockSpec((1, K, G, D),
                            lambda b, j, vl, wb, wo, bt: (b, 0, 0, 0))
    new_row = pl.BlockSpec((1, 1, K, D),
                           lambda b, j, vl, wb, wo, bt: (b, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, P),
            in_specs=[per_slot, new_row, new_row, page, page],
            out_specs=[per_slot, write_page, write_page],
            scratch_shapes=_paged_scratch(K, G, D),
        ),
        out_shape=out_shapes,
        # pools are donated: inputs 7/8 of (vlen, wblk, woff, bt, q, kn,
        # vn, k_pool, v_pool) become outputs 1/2 — the kernel rewrites
        # only each slot's private write page (plus the trash page)
        input_output_aliases={7: 1, 8: 2},
        interpret=interpret,
    )(vlen, wblk, woff, bt, q, kn, vn, k_pool, v_pool)
