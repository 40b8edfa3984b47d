"""Pallas kernel validation: interpret-mode sweep over shapes/dtypes against
the pure-jnp oracles in ``repro.kernels.ref``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.ops import flash_attention_grouped


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _qkv(rng, B, H, K, S, T, D, dtype):
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, H, S, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, K, T, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, K, T, D), jnp.float32).astype(dtype)
    return q, k, v


FLASH_CASES = [
    # (B, H, K, S, T, D, causal)
    (1, 4, 4, 128, 128, 64, True),        # MHA causal
    (2, 8, 2, 256, 256, 64, True),        # GQA group=4
    (1, 4, 1, 128, 256, 128, False),      # MQA, rectangular, bidirectional
    (1, 2, 2, 256, 512, 64, True),        # long KV
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention(case, dtype):
    B, H, K, S, T, D, causal = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    q, k, v = _qkv(rng, B, H, K, S, T, D, dtype)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


def test_flash_attention_valid_len():
    B, H, K, S, T, D = 1, 4, 2, 128, 256, 64
    q, k, v = _qkv(jax.random.PRNGKey(0), B, H, K, S, T, D, jnp.float32)
    vlen = 200
    out = flash_attention(q, k, v, valid_len=jnp.int32(vlen), causal=True,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, kv_valid_len=vlen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_q_offset():
    """Decode-like chunk: queries at positions [offset, offset+S)."""
    B, H, K, S, T, D = 1, 2, 2, 128, 256, 64
    q, k, v = _qkv(jax.random.PRNGKey(1), B, H, K, S, T, D, jnp.float32)
    out = flash_attention(q, k, v, causal=True, q_offset=100, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, q_offset=100)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


DECODE_CASES = [
    # (B, K, G, T, D, valid)
    (1, 4, 1, 512, 64, 512),
    (2, 2, 4, 1024, 64, 700),     # GQA + partial cache
    (1, 8, 4, 512, 128, 300),
    (4, 1, 8, 2048, 64, 2048),    # MQA long cache
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_attention(case, dtype):
    B, K, G, T, D, valid = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, K, G, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, K, T, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, K, T, D), jnp.float32).astype(dtype)
    out = decode_attention(q, k, v, valid_len=jnp.int32(valid),
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


MM_CASES = [
    (128, 256, 128),
    (256, 512, 384),
    (128, 1024, 256),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", MM_CASES)
def test_int8_matmul(case, dtype):
    M, Kd, N = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2 = jax.random.split(rng)
    x = jax.random.normal(k1, (M, Kd), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (Kd, N), jnp.float32)
    w_q, scales = ref.quantize_int8(w)
    out = int8_matmul(x, w_q, scales, interpret=True)
    want = ref.int8_matmul_ref(x, w_q, scales)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-3)


def test_grouped_adapter_matches_model_layout():
    """ops.flash_attention_grouped == layers.attention_core (xla)."""
    from repro.models.layers import attention_core
    B, S, K, G, D = 2, 128, 2, 2, 64
    rng = jax.random.PRNGKey(7)
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, S, K, G, D), jnp.float32)
    k = jax.random.normal(k2, (B, S, K, D), jnp.float32)
    v = jax.random.normal(k3, (B, S, K, D), jnp.float32)
    got = flash_attention_grouped(q, k, v, causal=True, interpret=True)
    want = attention_core(q, k, v, causal=True, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_adapter_matches_model_layout():
    from repro.models.layers import attention_core
    B, K, G, T, D = 2, 2, 4, 256, 64
    rng = jax.random.PRNGKey(8)
    k1, k2, k3 = jax.random.split(rng, 3)
    q = jax.random.normal(k1, (B, 1, K, G, D), jnp.float32)
    k = jax.random.normal(k2, (B, T, K, D), jnp.float32)
    v = jax.random.normal(k3, (B, T, K, D), jnp.float32)
    got = flash_attention_grouped(q, k, v, causal=False, kv_valid_len=200,
                                  interpret=True)
    want = attention_core(q, k, v, causal=False, kv_valid_len=200, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


PAGED_DECODE_CASES = [
    # (B, K, G, n_pages, page_size, pages_per_slot, D)
    (2, 2, 4, 16, 64, 4, 64),
    (3, 4, 1, 8, 128, 2, 64),
    (1, 1, 8, 32, 32, 8, 128),    # MQA, fine pages
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", PAGED_DECODE_CASES)
def test_paged_decode_attention(case, dtype):
    """Block-table kernel == gathering each slot's pages into a
    contiguous cache and running the dense reference, including sentinel
    block-table entries past the per-slot valid length."""
    from repro.kernels.decode_attention import paged_decode_attention

    B, K, G, n_pages, ps, P, D = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    q = jax.random.normal(k1, (B, K, G, D), jnp.float32).astype(dtype)
    k_pool = jax.random.normal(k2, (n_pages, ps, K, D),
                               jnp.float32).astype(dtype)
    v_pool = jax.random.normal(k3, (n_pages, ps, K, D),
                               jnp.float32).astype(dtype)
    # each slot draws distinct pages; entries past its allocation carry
    # the sentinel n_pages (clamped by the kernel, masked by valid_len)
    perm = jax.random.permutation(k4, n_pages)[: B * P].reshape(B, P)
    valid = jax.random.randint(k5, (B,), 1, P * ps + 1)
    n_alloc = -(-valid // ps)                      # pages actually held
    bt = jnp.where(jnp.arange(P)[None, :] < n_alloc[:, None], perm,
                   n_pages)
    out = paged_decode_attention(q, k_pool, v_pool, bt, valid,
                                 interpret=True)
    # reference: gather pages (clamp sentinels) -> (B, K, T, D) dense
    gathered_k = jnp.take(k_pool, jnp.clip(bt, 0, n_pages - 1), axis=0)
    gathered_v = jnp.take(v_pool, jnp.clip(bt, 0, n_pages - 1), axis=0)
    kc = gathered_k.reshape(B, P * ps, K, D).transpose(0, 2, 1, 3)
    vc = gathered_v.reshape(B, P * ps, K, D).transpose(0, 2, 1, 3)
    wants = [ref.decode_attention_ref(q[i:i + 1], kc[i:i + 1],
                                      vc[i:i + 1], valid[i])
             for i in range(B)]
    want = jnp.concatenate(wants, axis=0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


FUSED_PAGED_CASES = [
    # (B, K, G, n_logical, page_size, pages_per_slot, D)
    (2, 2, 4, 12, 64, 4, 64),
    (3, 4, 1, 8, 128, 2, 64),
    (2, 1, 8, 16, 32, 8, 128),    # MQA, fine pages
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", FUSED_PAGED_CASES)
def test_fused_paged_decode_attention(case, dtype):
    """Fused write+attend kernel == XLA pool scatter followed by the
    masked paged attend, on a trash-page pool (one extra page at the
    sentinel index) with a mix of live, first-token, and sentinel slots.
    Checks the attention output, the written pool pages, and that no
    other live page is disturbed."""
    from repro.kernels.decode_attention import fused_paged_decode_attention
    from repro.models import kvcache as KV
    from repro.models.layers import paged_attention_core

    B, K, G, n_logical, ps, P, D = case
    n_phys = n_logical + 1            # + trash page == sentinel index
    sent = n_logical
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(rng, 7)
    q = jax.random.normal(k1, (B, K, G, D), jnp.float32).astype(dtype)
    k_pool = jax.random.normal(k2, (n_phys, ps, K, D),
                               jnp.float32).astype(dtype)
    v_pool = jax.random.normal(k3, (n_phys, ps, K, D),
                               jnp.float32).astype(dtype)
    k_new = jax.random.normal(k4, (B, K, D), jnp.float32).astype(dtype)
    v_new = jax.random.normal(k5, (B, K, D), jnp.float32).astype(dtype)
    # slot B-1 is inactive (all-sentinel row, its write lands in the
    # trash page); the rest hold exactly the pages their position needs
    perm = jax.random.permutation(k6, n_logical)[: B * P].reshape(B, P)
    pos = jax.random.randint(k7, (B,), 0, P * ps)
    pos = pos.at[0].set(0)                         # first-token slot
    n_alloc = pos // ps + 1
    bt = jnp.where(jnp.arange(P)[None, :] < n_alloc[:, None], perm, sent)
    bt = bt.at[B - 1].set(sent)

    out, kp2, vp2 = fused_paged_decode_attention(
        q, k_new, v_new, k_pool, v_pool, bt, pos, interpret=True)

    # reference path: scatter (sentinel rows land in the trash page on
    # this layout too), then the masked block-table attend
    kp_ref, vp_ref = KV.paged_update_layer_cache(
        k_pool, v_pool, k_new[:, None], v_new[:, None], bt, pos)
    o_ref = paged_attention_core(q[:, None], kp_ref, vp_ref, bt,
                                 kv_valid_len=pos + 1, impl="xla")[:, 0]

    live = sorted({int(p) for p in np.asarray(bt).ravel() if p < sent})
    idle = [p for p in range(n_logical) if p not in live]
    np.testing.assert_array_equal(np.asarray(kp2)[live],
                                  np.asarray(kp_ref)[live])
    np.testing.assert_array_equal(np.asarray(vp2)[live],
                                  np.asarray(vp_ref)[live])
    np.testing.assert_array_equal(np.asarray(kp2)[idle],
                                  np.asarray(k_pool)[idle])
    np.testing.assert_array_equal(np.asarray(vp2)[idle],
                                  np.asarray(v_pool)[idle])
    np.testing.assert_allclose(np.asarray(out, np.float32)[:B - 1],
                               np.asarray(o_ref, np.float32)[:B - 1],
                               **tol(dtype))


STACKED_CASES = {
    # (L, B, S, K, G, D): phi3-mini's and Yi-9B's attention widths at
    # their served lengths, few layers and slots
    "phi3": (2, 6, 512, 32, 1, 96),
    "yi": (2, 6, 2048, 4, 8, 128),
}


@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("widths", sorted(STACKED_CASES))
def test_stacked_decode_attention_matches_masked_xla(widths, layer):
    """The length-aware decode over a stacked (L, B, S, K, D) carry ==
    attention_core's masked XLA attention over the layer's slice, for
    slots of length 1, a block edge +- 1 and S; a dead slot (length 0)
    returns zeros and is not compared."""
    from repro.kernels.decode_attention import (stacked_block_len,
                                                stacked_decode_attention,
                                                stacked_decode_plan)
    from repro.models.layers import attention_core

    L, B, S, K, G, D = STACKED_CASES[widths]
    ps = stacked_block_len(S, K, D, 2)
    assert 1 < ps < S and S % ps == 0
    layer = 0 if layer == "first" else L - 1
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(L * S + K), 3)
    ks = jax.random.normal(k1, (L, B, S, K, D), jnp.float32).astype(
        jnp.bfloat16)
    vs = jax.random.normal(k2, (L, B, S, K, D), jnp.float32).astype(
        jnp.bfloat16)
    q = jax.random.normal(k3, (B, K, G, D), jnp.float32).astype(jnp.bfloat16)
    valid = jnp.asarray([0, 1, ps - 1, ps, ps + 1, S], jnp.int32)
    want = attention_core(q[:, None], ks[layer], vs[layer], causal=False,
                          kv_valid_len=valid)[:, 0]
    live = np.asarray(valid) > 0
    out = stacked_decode_attention(q, ks, vs, layer,
                                   stacked_decode_plan(valid, ks),
                                   interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=2e-2, atol=2e-2)
    assert not np.asarray(out, np.float32)[~live].any()


@pytest.mark.parametrize("valid", [
    [0, 17, 0, 0, 64, 1],      # dead slots between and before live ones
    [0, 0, 0, 0, 0, 0],        # nothing live: one row runs, computes nothing
], ids=["mixed", "all-dead"])
def test_stacked_plan_fetches_only_live_blocks(valid):
    """Walked in grid order (row-major) over the grid that runs, the block
    ids change, and so a block is fetched, only at each live slot's live
    blocks: dead slots get no row, and blocks past a slot's length repeat
    its last. The plan's ``positions_read`` counts those blocks."""
    from repro.kernels.decode_attention import stacked_decode_plan
    B, P, ps = 6, 4, 16
    valid = jnp.asarray(valid, jnp.int32)
    stack = jax.ShapeDtypeStruct((2, B, P * ps, 2, 16), jnp.float32)
    plan = stacked_decode_plan(valid, stack)     # blocks of 16 here
    n_live = int(np.sum(np.asarray(valid) > 0))
    rows, cols = int(plan.n_rows), int(plan.n_cols)
    assert (rows, cols) == ((3, 4) if n_live else (1, 1))
    table = np.asarray(plan.table)[:rows, :cols]
    order = table.reshape(-1)
    fetched = 1 + int(np.sum(order[1:] != order[:-1]))
    live_blocks = int(np.sum(-(-np.asarray(valid) // ps)))
    assert fetched == max(live_blocks, 1)
    assert int(plan.positions_read) == live_blocks * ps
    # each row reads its own slot's blocks, at its slot's length, live
    # slots first in slot order
    for i in range(rows):
        b = int(plan.rows[i])
        assert int(plan.row_len[i]) == int(valid[b])
        assert (table[i] // P == b).all()
    assert sorted(np.asarray(plan.rows).tolist()) == list(range(B))
    if n_live:
        assert np.asarray(plan.rows)[:rows].tolist() == [1, 4, 5]
