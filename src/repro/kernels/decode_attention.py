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

``stacked_decode_attention`` runs the block-table kernel over the
contiguous decode carry: a stacked (L, B, S, K, D) cache reshapes for free
into a pool of (ps, K, D) blocks, the grid runs only the live slots, and
each slot's table walks only the blocks below its valid length. A block
index repeated across consecutive grid steps is not fetched again, so the
blocks past a slot's length cost no DMA, and a slot with nothing live
costs none at all.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

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


def _paged_decode_kernel(off_ref, vlen_ref, bt_ref, slot_ref, q_ref, k_ref,
                         v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                         page_size: int, sm_scale: float):
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

    @pl.when(tj == pl.num_programs(1) - 1)
    def _finish():
        _finish_heads(o_ref, l_scr, acc_scr)


def _paged_call(q, k_pool, v_pool, table, vlen, slots, grid, interpret,
                offset=0):
    """The block-table flash-decode over ``grid`` = (rows, blocks): grid
    row ``i`` serves slot ``slots[i]`` (its query and output) at valid
    length ``vlen[i]``, walking pool pages ``offset + table[i, :]``. Either
    grid bound may be a traced scalar."""
    B, K, G, D = q.shape
    page_size = k_pool.shape[1]
    row = pl.BlockSpec((1, K, G, D),
                       lambda i, j, off, vl, bt, sl: (sl[i], 0, 0, 0))
    page = pl.BlockSpec((1, page_size, K, D),
                        lambda i, j, off, vl, bt, sl:
                        (off[0] + bt[i, j], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, page_size=page_size,
                          sm_scale=D ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[row, page, page],
            out_specs=row,
            scratch_shapes=_paged_scratch(K, G, D),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(offset, jnp.int32), (1,)), vlen, table, slots,
      q, k_pool, v_pool)


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
    the slot's *logical* sequence of P * page_size positions. A slot of
    valid length 0 computes nothing and returns zeros.

    Returns (B, K, G, D).
    """
    B = q.shape[0]
    n_pages, page_size = k_pool.shape[:2]
    P = block_table.shape[1]
    if valid_len is None:
        valid_len = jnp.full((B,), P * page_size, jnp.int32)
    else:
        valid_len = jnp.broadcast_to(
            jnp.asarray(valid_len, jnp.int32), (B,))
    bt = jnp.clip(block_table.astype(jnp.int32), 0, n_pages - 1)
    return _paged_call(q, k_pool, v_pool, bt, valid_len,
                       jnp.arange(B, dtype=jnp.int32), (B, P), interpret)


# Largest KV tile the stacked decode fetches in one DMA: double-buffered K
# and V then hold 4 MiB of VMEM.
STACKED_TILE_MAX_BYTES = 1 << 20


def stacked_block_len(max_len: int, n_kv: int, head_dim: int,
                      itemsize: int) -> int:
    """Block length of the stacked decode for a (.., max_len, n_kv,
    head_dim) cache: the largest power of two that divides ``max_len``,
    leaves at least four blocks per slot (a short live prefix reads a
    quarter of the slot or less) and keeps one (block, K, D) tile, with D
    padded to the 128 lanes, within ``STACKED_TILE_MAX_BYTES``. phi3-mini
    (K 32, D 96, 512 positions, bf16) gets 128; Yi-9B (K 4, D 128, 2,048)
    gets 512."""
    row = n_kv * -(-head_dim // 128) * 128 * itemsize
    ps = 1
    while (max_len % (2 * ps) == 0 and 2 * ps <= max(max_len // 4, 1)
           and 2 * ps * row <= STACKED_TILE_MAX_BYTES):
        ps *= 2
    return ps


class StackedPlan(NamedTuple):
    """One decode step's walk of the stacked cache, the same for every
    layer: built once per step (``stacked_decode_plan``), outside the layer
    scan, so a layer adds nothing to the kernel call but its index."""
    valid_len: jax.Array   # (B,) positions each slot attends
    rows: jax.Array        # (B,) slot each grid row serves
    row_len: jax.Array     # (B,) valid length of each grid row's slot
    table: jax.Array       # (B, P) layer-0 block of each grid row, column
    n_rows: jax.Array      # grid rows that run
    n_cols: jax.Array      # grid columns that run
    positions_read: jax.Array  # KV positions a layer's call fetches: the
                               # live slots' blocks, in positions


def stacked_decode_plan(valid_len: jax.Array, k_stack: Any) -> StackedPlan:
    """The grid of ``stacked_decode_attention`` for per-slot ``valid_len``
    (B,) over a stacked (L, B, S, K, D) cache (``k_stack``: the array or its
    shape and dtype), in blocks of ``stacked_block_len``.

    The grid's rows run the live slots only, in slot order, and its columns
    only as far as the longest of them, so a slot with nothing live fetches
    nothing. Row ``i`` walks its slot's blocks in the (L * B * P, block, K,
    D) view of the stack, layer 0's ids (layer ``l`` adds ``l * B * P``),
    up to the slot's last live block, and repeats that block to the end of
    the row, where Pallas fetches nothing."""
    _L, B, S, K, D = k_stack.shape
    block = stacked_block_len(S, K, D, jnp.dtype(k_stack.dtype).itemsize)
    P = S // block
    vlen = jnp.asarray(valid_len, jnp.int32)
    nblk = (vlen + block - 1) // block                          # (B,)
    live = nblk > 0
    j = jnp.arange(P, dtype=jnp.int32)
    table = jnp.arange(B, dtype=jnp.int32)[:, None] * P + jnp.minimum(
        j[None, :], jnp.maximum(nblk - 1, 0)[:, None])
    rows = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return StackedPlan(vlen, rows, vlen[rows], table[rows],
                       jnp.maximum(jnp.sum(live.astype(jnp.int32)), 1),
                       jnp.maximum(jnp.max(nblk), 1),
                       jnp.sum(nblk) * block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def stacked_decode_attention(q: jax.Array, k_stack: jax.Array,
                             v_stack: jax.Array, layer: Any,
                             plan: StackedPlan, *,
                             interpret: bool = False) -> jax.Array:
    """Decode attention of one layer of a stacked contiguous KV cache,
    reading only each slot's blocks below its valid length.

    q: (B, K, G, D), one query token per slot; k_stack/v_stack: (L, B, S,
    K, D), the whole carry of a decode layer scan; ``layer``: scalar layer
    index; ``plan``: the step's ``stacked_decode_plan`` of each slot's
    valid length (``pos + 1`` for a live slot, 0 for one with nothing
    live, whose output is zeros).

    The stack is viewed as a (L * B * S / block, block, K, D) page pool, a
    reshape that moves no bytes, and the block-table kernel of
    ``paged_decode_attention`` walks it, layer ``layer``'s blocks found by
    an offset in the index map.

    Returns (B, K, G, D).
    """
    L, B, S, K, D = k_stack.shape
    P = plan.table.shape[1]
    pool_shape = (L * B * P, S // P, K, D)
    out = _paged_call(q, k_stack.reshape(pool_shape),
                      v_stack.reshape(pool_shape), plan.table, plan.row_len,
                      plan.rows, (plan.n_rows, plan.n_cols), interpret,
                      offset=layer * (B * P))
    # a slot no grid row served holds whatever the buffer held
    return jnp.where((plan.valid_len > 0)[:, None, None, None], out, 0)


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
