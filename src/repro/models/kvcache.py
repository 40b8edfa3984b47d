"""KV-cache and recurrent-state containers.

Caches are plain pytrees (dicts of arrays) so they cross pjit/shard_map
boundaries and checkpoint naturally. Attention caches come in two layouts:

* contiguous — (L, B, S_max, K, D), layer-major: a per-layer scan either
  consumes them as scan xs and emits updated slices as ys, or (dense
  decode) carries the whole stack and writes each layer's new token in
  place. Every batch slot owns a private ``S_max`` run of positions.
* paged — a shared page pool (L, n_pages, page_size, K, D) plus a per-slot
  block table (B, S_max // page_size) of page indices. Logical position
  ``p`` of slot ``b`` lives at ``pool[bt[b, p // page_size], p % page_size]``.
  Unallocated block-table entries carry the sentinel ``n_pages`` (one past
  the pool): writes routed there are dropped (scatter ``mode="drop"``) and
  reads clamp to the last page, whose values are always masked off by the
  caller's ``kv_valid_len``. The pool is shared across batch slots, so slot
  count is no longer bound by worst-case context length — the serving
  engine's page allocator hands pages to slots as their ``pos`` grows.

Page reclaim is safe at any host boundary, including *mid-stream preempts*
(optimistic admission frees a live victim's pages): pointing the victim's
block-table row back at the sentinel detaches it from the pool without
touching neighbors, and a reclaimed page can be handed to another slot
immediately — its stale contents sit behind the new holder's write
frontier, and every position is rewritten by the new holder before any
masked read (``kv_valid_len``) can include it. This is the same argument
that makes slot reuse exact, applied page-at-a-time.

Recurrent families' O(1) states (SSM, conv tails, xLSTM cells) have no
sequence axis and stay batch-indexed in either layout.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Cache = Dict[str, Any]


def alloc_attn_cache(n_layers: int, batch: int, max_len: int, n_kv: int,
                     head_dim: int, dtype) -> Cache:
    shape = (n_layers, batch, max_len, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def alloc_paged_attn_cache(n_layers: int, n_pages: int, page_size: int,
                           n_kv: int, head_dim: int, dtype) -> Cache:
    """Shared page pool: (L, n_pages, page_size, K, D) per leaf."""
    shape = (n_layers, n_pages, page_size, n_kv, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def update_layer_cache(k_cache: jax.Array, v_cache: jax.Array,
                       k_new: jax.Array, v_new: jax.Array,
                       pos: Any) -> Tuple[jax.Array, jax.Array]:
    """Write (B, S_new, K, D) at position ``pos`` of a (B, S_max, K, D) buffer.

    ``pos`` is either a shared scalar position (run-to-completion waves, all
    sequences in lockstep) or a (B,) vector of per-sequence positions
    (continuous batching: every batch slot is at its own decode offset). The
    vector form lowers to a per-row scatter via vmap.
    """
    if jnp.ndim(pos) == 0:
        k_cache = lax.dynamic_update_slice_in_dim(
            k_cache, k_new.astype(k_cache.dtype), pos, axis=1)
        v_cache = lax.dynamic_update_slice_in_dim(
            v_cache, v_new.astype(v_cache.dtype), pos, axis=1)
        return k_cache, v_cache
    write = jax.vmap(
        lambda c, n, p: lax.dynamic_update_slice_in_dim(c, n, p, axis=0))
    k_cache = write(k_cache, k_new.astype(k_cache.dtype), pos)
    v_cache = write(v_cache, v_new.astype(v_cache.dtype), pos)
    return k_cache, v_cache


def update_stacked_layer_cache(k_cache: jax.Array, v_cache: jax.Array,
                               k_new: jax.Array, v_new: jax.Array,
                               layer: Any,
                               pos: Any) -> Tuple[jax.Array, jax.Array]:
    """Write one token's (B, 1, K, D) k/v into layer ``layer`` of a stacked
    (L, B, S_max, K, D) cache, touching only the written rows.

    The layer-indexed twin of :func:`update_layer_cache` for a decode scan
    that carries the whole stacked cache: a scalar ``pos`` is one
    ``dynamic_update_slice`` at ``(layer, 0, pos)``, a (B,) ``pos`` a
    per-slot scatter. ``pos`` is clipped to ``[0, S_max - 1]`` so a slot at
    (or past) the end writes where the clamping per-layer write did.
    """
    S = k_cache.shape[2]
    pos = jnp.clip(pos, 0, S - 1)
    if jnp.ndim(pos) == 0:
        start = (layer, 0, pos, 0, 0)
        k_cache = lax.dynamic_update_slice(
            k_cache, k_new[None].astype(k_cache.dtype), start)
        v_cache = lax.dynamic_update_slice(
            v_cache, v_new[None].astype(v_cache.dtype), start)
        return k_cache, v_cache
    rows = jnp.arange(k_cache.shape[1])
    k_cache = k_cache.at[layer, rows, pos].set(
        k_new[:, 0].astype(k_cache.dtype), unique_indices=True,
        mode="promise_in_bounds")
    v_cache = v_cache.at[layer, rows, pos].set(
        v_new[:, 0].astype(v_cache.dtype), unique_indices=True,
        mode="promise_in_bounds")
    return k_cache, v_cache


def page_coords(block_table: jax.Array, pos: Any,
                page_size: int) -> Tuple[jax.Array, jax.Array]:
    """(page, offset) of logical position ``pos`` per slot.

    block_table: (B, P) page indices; ``pos`` a scalar or (B,) vector.
    Slots whose block-table entry is the sentinel (== n_pages) keep it, so
    downstream scatters drop the write.
    """
    B = block_table.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    blk = jnp.clip(pos // page_size, 0, block_table.shape[1] - 1)
    page = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    return page, pos % page_size


def paged_update_layer_cache(k_pool: jax.Array, v_pool: jax.Array,
                             k_new: jax.Array, v_new: jax.Array,
                             block_table: jax.Array,
                             pos: Any) -> Tuple[jax.Array, jax.Array]:
    """Write one token's (B, 1, K, D) k/v at logical ``pos`` of each slot
    into a shared (n_pages, page_size, K, D) pool through the block table.

    The engine's page allocator guarantees no page is referenced by two
    live slots, so the per-slot scatters never collide; sentinel pages
    (freed or never-allocated slots) drop the write.
    """
    page, off = page_coords(block_table, pos, k_pool.shape[1])
    k_pool = k_pool.at[page, off].set(k_new[:, 0].astype(k_pool.dtype),
                                      mode="drop")
    v_pool = v_pool.at[page, off].set(v_new[:, 0].astype(v_pool.dtype),
                                      mode="drop")
    return k_pool, v_pool


def paged_update_window(k_pool: jax.Array, v_pool: jax.Array,
                        k_new: jax.Array, v_new: jax.Array,
                        block_table: jax.Array,
                        pos: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Write an S-token window's (B, S, K, D) k/v starting at logical
    ``pos[b]`` of each slot into a shared (n_pages, page_size, K, D) pool.

    The speculative-verify generalization of the one-token
    :func:`paged_update_layer_cache`: window token ``s`` of slot ``b``
    lands at logical position ``pos[b] + s``, routed through the block
    table page by page. The engine guarantees the window's pages are
    attached before speculating (it tops up coverage through
    ``pos + S - 1``); any residual sentinel entries drop the write.
    """
    ps = k_pool.shape[1]
    B, S = k_new.shape[:2]
    pos_bs = pos[:, None].astype(jnp.int32) + jnp.arange(S,
                                                         dtype=jnp.int32)
    blk = jnp.clip(pos_bs // ps, 0, block_table.shape[1] - 1)
    page = jnp.take_along_axis(block_table, blk, axis=1)      # (B, S)
    off = pos_bs % ps
    k_pool = k_pool.at[page, off].set(k_new.astype(k_pool.dtype),
                                      mode="drop")
    v_pool = v_pool.at[page, off].set(v_new.astype(v_pool.dtype),
                                      mode="drop")
    return k_pool, v_pool


def sentinel_block_table(n_rows: int, pages_per_slot: int,
                         n_pages: int) -> np.ndarray:
    """All-sentinel block table rows (host-side, int32): every entry is
    ``n_pages`` — one past the pool — so writes drop and masked reads
    clamp. The serving engine starts every slot here and returns a slot's
    row here whenever its pages are reclaimed: at sequence finish *and* at
    preemption, where the request is parked and its pages handed out
    while it waits (safe per the module docstring's rewrite-before-read
    argument)."""
    return np.full((n_rows, pages_per_slot), n_pages, np.int32)


def reset_slot_rows(leaf: jax.Array, batch_axis: int, take: jax.Array,
                    empty_row: jax.Array) -> jax.Array:
    """Replace the batch rows of a slot-indexed state leaf selected by
    ``take`` (B,) bool with ``empty_row`` (the leaf's 1-row empty state,
    batch axis leading).

    This is the in-segment slot-reset primitive: when the serving engine's
    fused decode loop pulls a staged request into a freed slot, the slot's
    O(1) recurrent-state rows (SSM/conv/xLSTM cells) must restart from the
    family's empty state *inside* the traced loop body. Attention KV leaves
    need no reset — a position is always rewritten by its new occupant
    before any masked read can include it — so callers skip leaves that
    carry a sequence axis.
    """
    arr = jnp.moveaxis(leaf, batch_axis, 0)
    cond = take.reshape((-1,) + (1,) * (arr.ndim - 1))
    arr = jnp.where(cond, empty_row.astype(arr.dtype), arr)
    return jnp.moveaxis(arr, 0, batch_axis)


def gather_pool_view(pool: jax.Array, block_table: jax.Array,
                     batch_axis: int, seq_axis: int) -> jax.Array:
    """Materialize a contiguous-layout leaf from a paged pool leaf.

    ``pool`` is a pool-shaped leaf whose page axis sits at ``seq_axis - 1``
    and whose in-page offset axis at ``seq_axis`` (the engine's
    ``_pool_shape`` puts them where the contiguous leaf's sequence axis
    was, after dropping the batch axis). The result has the batch axis at
    ``batch_axis`` and a ``P * page_size`` sequence axis at ``seq_axis`` —
    exactly the contiguous-layout leaf shape, so a decode loop can run the
    *contiguous* update/attend program over it. Sentinel block-table
    entries clamp to the last page; their positions always sit at or past
    the caller's ``kv_valid_len`` and mask to exact zeros downstream.
    """
    pa = seq_axis - 1
    pool2 = jnp.moveaxis(pool, (pa, pa + 1), (0, 1))     # (n_pages, ps, ..)
    bt = jnp.clip(block_table, 0, pool.shape[pa] - 1)
    pages = jnp.take(pool2, bt, axis=0)                  # (B, P, ps, ..)
    B, P = block_table.shape
    view = pages.reshape((B, P * pool.shape[pa + 1]) + pool2.shape[2:])
    return jnp.moveaxis(view, (0, 1), (batch_axis, seq_axis))


def scatter_pool_view(pool: jax.Array, view: jax.Array,
                      block_table: jax.Array, batch_axis: int,
                      seq_axis: int, start: jax.Array,
                      stop: jax.Array) -> jax.Array:
    """Write positions ``[start[b], stop[b])`` of each slot's contiguous
    view back into the paged pool through the block table.

    The inverse of :func:`gather_pool_view`, restricted to the span a
    decode segment actually wrote: the fused loop decodes on the gathered
    view and flushes only ``[segment entry pos, exit pos)`` per slot, so
    pages the slot no longer owns (released mid-segment) and pages it
    shares read-only with other slots (prefix cache) are never touched.
    Positions routed to sentinel entries drop, as with the per-step
    scatter path.
    """
    pa = seq_axis - 1
    n_pages, ps = pool.shape[pa], pool.shape[pa + 1]
    v2 = jnp.moveaxis(view, (batch_axis, seq_axis), (0, 1))   # (B, S, ..)
    B, S = v2.shape[:2]
    poss = jnp.arange(S, dtype=jnp.int32)[None, :]            # (1, S)
    in_range = (start[:, None] <= poss) & (poss < stop[:, None])
    blk = jnp.clip(poss // ps, 0, block_table.shape[1] - 1)
    page = jnp.take_along_axis(block_table,
                               jnp.broadcast_to(blk, (B, S)), axis=1)
    page = jnp.where(in_range, page, n_pages)                 # drop rest
    off = jnp.broadcast_to(poss % ps, (B, S))
    pool2 = jnp.moveaxis(pool, (pa, pa + 1), (0, 1))
    pool2 = pool2.at[page, off].set(v2.astype(pool2.dtype), mode="drop")
    return jnp.moveaxis(pool2, (0, 1), (pa, pa + 1))


def copy_pool_page(pool: jax.Array, src: jax.Array, dst: jax.Array,
                   seq_axis: int) -> jax.Array:
    """Copy one physical page of a pool leaf (``src`` -> ``dst``), page
    axis at ``seq_axis - 1``: the device half of copy-on-write, run when a
    cache-hit admission must rewrite a position inside a shared page."""
    pa = seq_axis - 1
    pool2 = jnp.moveaxis(pool, pa, 0)
    row = lax.dynamic_index_in_dim(pool2, src, axis=0, keepdims=False)
    pool2 = pool2.at[dst].set(row, mode="drop")
    return jnp.moveaxis(pool2, 0, pa)


def gather_pool_pages(pool: jax.Array, page_ids: Any,
                      seq_axis: int) -> np.ndarray:
    """Copy the listed physical pages of a pool leaf to host memory.

    ``pool`` is pool-shaped (page axis at ``seq_axis - 1``, in-page offset
    axis at ``seq_axis``); ``page_ids`` is a list/array of physical page
    indices. Returns a host ``np.ndarray`` of shape ``(n, page_size, ...)``
    with the page and offset axes leading — the swap-out payload. The bytes
    are *copied* off-device, so the pages themselves can be reclaimed (and
    rewritten by new holders, including refcounted prefix-cache sharers)
    immediately after the gather.
    """
    pa = seq_axis - 1
    pool2 = jnp.moveaxis(pool, (pa, pa + 1), (0, 1))
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    return np.asarray(jnp.take(pool2, ids, axis=0))


def scatter_pool_pages(pool: jax.Array, host_pages: np.ndarray,
                       page_ids: Any, seq_axis: int) -> jax.Array:
    """Write host page bytes (from :func:`gather_pool_pages`) back into a
    pool leaf at the listed physical pages — the swap-in half of host-RAM
    KV swap. The destination pages are freshly allocated by the caller, so
    the scatter never collides with a live holder; out-of-range ids (the
    sentinel) drop, matching every other pool scatter.
    """
    pa = seq_axis - 1
    pool2 = jnp.moveaxis(pool, (pa, pa + 1), (0, 1))
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    pool2 = pool2.at[ids].set(jnp.asarray(host_pages, pool2.dtype),
                              mode="drop")
    return jnp.moveaxis(pool2, (0, 1), (pa, pa + 1))


def gather_state_rows(leaf: jax.Array, batch_axis: int,
                      slot: int) -> np.ndarray:
    """Copy one slot's row of an O(1) recurrent-state leaf (SSM/conv/xLSTM
    cells — no sequence axis) to host memory: the swap-out payload for
    non-attention state."""
    arr = jnp.moveaxis(leaf, batch_axis, 0)
    return np.asarray(arr[slot])


def scatter_state_rows(leaf: jax.Array, batch_axis: int, slot: int,
                       host_row: np.ndarray) -> jax.Array:
    """Write a host state row (from :func:`gather_state_rows`) back into
    ``slot`` of a batch-indexed recurrent-state leaf."""
    arr = jnp.moveaxis(leaf, batch_axis, 0)
    arr = arr.at[slot].set(jnp.asarray(host_row, arr.dtype))
    return jnp.moveaxis(arr, 0, batch_axis)


def gather_block_kv(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Materialize each slot's logical KV view from the shared pool.

    pool: (n_pages, page_size, K, D); block_table: (B, P).
    Returns (B, P * page_size, K, D) — the same shape the contiguous layout
    attends over (P * page_size == S_max), so the attention computation is
    unchanged downstream. Sentinel entries clamp to the last page; their
    positions are always >= the caller's ``kv_valid_len`` and mask out.
    """
    B, P = block_table.shape
    ps = pool.shape[1]
    pages = jnp.take(pool, block_table, axis=0, mode="clip")  # (B,P,ps,K,D)
    return pages.reshape((B, P * ps) + pool.shape[2:])
