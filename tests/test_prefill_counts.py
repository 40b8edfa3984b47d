"""Prefill's token counters, and the chunked attention that causal prefill
takes where its dense scores would pass their budget.

``prefill_prompt_tokens`` counts the real prompt tokens each prefill
dispatch admits; ``prefill_computed_tokens`` counts the token rows it
computes: its rows (1 for a lone request, ``max_batch`` for a group) times
its bucket length, padding included. Together they give the share of
prefill work spent on padding.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.models import build_model
from repro.models import layers as L
from repro.serving.engine import Request, ServingEngine

YI = ARCHS["yi-9b"].reduced()


@pytest.fixture(scope="module")
def yi():
    model = build_model(YI)
    return model, model.init(jax.random.PRNGKey(0))


def test_prefill_counters_match_hand_counts(yi):
    model, params = yi
    eng = ServingEngine(model, params, max_batch=4, max_len=64,
                        decode_block=4, min_bucket=8)
    rng = np.random.default_rng(3)

    def req(rid, n):
        return Request(rid=rid, prompt=rng.integers(0, YI.vocab, n)
                       .astype(np.int32), max_new_tokens=3)
    # one admission pass: lengths 5 and 7 share bucket 8 (a group: 4 rows),
    # 12 is alone in bucket 16, 30 alone in bucket 32
    out = eng.serve([req(0, 5), req(1, 7), req(2, 12), req(3, 30)])
    assert len(out) == 4
    assert eng.stats["prefill_dispatches"] == 3
    assert eng.stats["prefill_prompt_tokens"] == 5 + 7 + 12 + 30
    assert eng.stats["prefill_computed_tokens"] == 4 * 8 + 16 + 32
    # later, a lone request of 3 tokens in bucket 8
    eng.serve([req(4, 3)])
    assert eng.stats["prefill_dispatches"] == 4
    assert eng.stats["prefill_prompt_tokens"] == 54 + 3
    assert eng.stats["prefill_computed_tokens"] == 80 + 8


@pytest.mark.parametrize("rows,bucket,chunked", [
    (8, 2048, True), (1, 2048, False), (8, 1024, False), (8, 512, False)])
def test_long_causal_attention_path_is_chosen_by_score_bytes(
        rows, bucket, chunked):
    """At Yi-9B's widths (4 KV heads of 128, 8 query heads each) a causal
    self-attention streams its scores in blocks only where the dense
    float32 scores would pass 1 GiB: 8 rows of a 2,048 bucket (4 GiB), and
    not one row of it (0.5 GiB) or 8 rows of 1,024 (1 GiB)."""
    K, G, D = 4, 8, 128

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    hlo = jax.jit(lambda *a: L.attention_core(*a, causal=True)).lower(
        sds(rows, bucket, K, G, D), sds(rows, bucket, K, D),
        sds(rows, bucket, K, D)).as_text()
    assert ("while" in hlo) == chunked      # the blocked scan, not S x S


def test_long_causal_attention_takes_the_chunked_path_and_matches(
        monkeypatch):
    """Past its score budget a causal self-attention streams its scores in
    blocks; the result is the plain softmax attention's."""
    monkeypatch.setattr(L, "DENSE_SCORES_MAX_BYTES", 0)
    B, S, K, G, D = 2, 512, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, K, G, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.float32)
    hlo = jax.jit(lambda *a: L.attention_core(*a, causal=True)) \
        .lower(q, k, v).as_text()
    assert "while" in hlo               # the blocked scan, not one S x S
    got = L.attention_core(q, k, v, causal=True)
    s = jnp.einsum("bskgd,btkd->bkgst", q, k,
                   precision=jax.lax.Precision.HIGHEST) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    want = jnp.einsum("bkgst,btkd->bskgd", jax.nn.softmax(s, -1), v,
                      precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


def test_prefill_logits_at_a_long_bucket_match_the_dense_path(
        yi, monkeypatch):
    """A 300-token prompt prefilled at bucket 512 gives the same
    next-token logits through chunked attention as through the dense
    path."""
    model, params = yi
    prompt = np.random.default_rng(5).integers(0, YI.vocab, 300)
    toks = np.zeros((1, 512), np.int32)
    toks[0, :300] = prompt

    def last_logits():
        logits, _ = model.prefill(params, {
            "tokens": jnp.asarray(toks),
            "length": jnp.asarray([300], jnp.int32)})
        return np.asarray(logits[0, -1])
    dense = last_logits()
    monkeypatch.setattr(L, "DENSE_SCORES_MAX_BYTES", 0)
    np.testing.assert_allclose(last_logits(), dense, atol=1e-4, rtol=0)
