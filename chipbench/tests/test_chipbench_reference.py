"""The plain float32 reference against the program's own model at a small
size on the CPU: prefill of a prompt, then greedy decode through the
program's KV cache, must give the logits the reference gives for the
whole sequence at once: int8 projections with float32 scales as the
configuration serves them, and bfloat16 projections with grouped
attention, the reference's other paths."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts src/ and chipbench/ on the path)
from harness import spec
from harness import weights as W
from harness.serve import arch_config, to_program_params

from repro.models.model import build_model

# Both sides compute in float32 on the CPU, with the same weights (the
# bfloat16 / int8 values are exact in float32); the only differences are
# the order of summation (the program's scanned, cached decode against the
# reference's whole-sequence matmuls) and the program's float32 rounding
# of RoPE angles per position. Over two layers that is ~1e-6 of logits
# whose spread is ~1; 2e-4 leaves room and still fails a wrong cache
# entry, a wrong position or a dropped norm by orders of magnitude.
ATOL = 2e-4


def _setup(cell, projections, kv_heads=4):
    bench, cfg, _mix, _c = chipbench_tiny.tiny(cell, kv_heads)
    cfg["precision"]["projections"] = projections
    cfg["precision"]["activations"] = "float32"
    dm = W.dims(cfg)
    arch = arch_config(cfg)
    if projections == "int8":
        arch = dataclasses.replace(arch, quantize="int8")
    model = build_model(arch)
    tree = W.make(dm, seed=5)
    params = to_program_params(jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree))
    return cfg, dm, model, params


@pytest.mark.parametrize("projections,kv_heads", [
    ("bfloat16", 2), ("int8", 4)])
def test_prefill_then_decode_matches_reference(projections, kv_heads):
    cfg, dm, model, params = _setup("phi3-int8-chat", projections, kv_heads)
    ref = spec.reference(cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, dm.vocab, 11).astype(np.int32)
    n_new = 6
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]})
    max_len = 32
    cache = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0)] * 2 + [(0, max_len - c.shape[2])]
                          + [(0, 0)] * (c.ndim - 3)), cache)
    toks, got = [], [np.asarray(logits[0, -1])]
    tok = int(jnp.argmax(logits[0, -1]))
    for i in range(n_new - 1):
        toks.append(tok)
        lg, cache = model.decode(params, cache, jnp.array([[tok]], jnp.int32),
                                 jnp.int32(len(prompt) + i))
        got.append(np.asarray(lg[0, -1]))
        tok = int(jnp.argmax(lg[0, -1]))
    seq = np.concatenate([prompt, np.array(toks, np.int32)])

    outer = W.outer(dm, 5)
    x = ref.embed(jnp.asarray(seq), outer["embed"])
    for i in range(dm.layers):
        lw = W.layer(dm, 5, i)
        w = {n: ref.dequant(lw[n], W.CONTRACT[n]) for n in W.CONTRACT}
        w.update(ln1=lw["ln1"], ln2=lw["ln2"])
        x = ref.layer(x, w, dm.rope_theta, dm.eps)
    want = np.asarray(ref.logits(x[len(prompt) - 1:], outer["ln_f"],
                                 outer["head"], dm.eps))
    np.testing.assert_allclose(np.stack(got), want, atol=ATOL, rtol=0)


def test_layer_alone_equals_layer_in_the_tree():
    """The reference remakes each layer from the seed; it must be the very
    tree the program was handed, bit for bit."""
    bench, cfg, _m, _c = chipbench_tiny.tiny("phi3-int8-chat")
    dm = W.dims(cfg)
    tree = W.make(dm, seed=2**41 + 9)
    for i in range(dm.layers):
        alone = W.layer(dm, 2**41 + 9, i)
        inside = jax.tree.map(lambda a: a[i], tree["layers"])
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(inside)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    outer = W.outer(dm, 2**41 + 9)
    for k in ("embed", "head", "ln_f"):
        np.testing.assert_array_equal(np.asarray(outer[k]),
                                      np.asarray(tree[k]))


def test_weights_differ_by_seed_and_are_served_types():
    bench, cfg, _m, _c = chipbench_tiny.tiny("phi3-int8-chat")
    dm = W.dims(cfg)
    a, b = W.make(dm, 1), W.make(dm, 2)
    assert a["layers"]["wq"]["w_q"].dtype == jnp.int8
    assert a["layers"]["wq"]["scales"].dtype == jnp.float32
    assert a["embed"].dtype == jnp.bfloat16
    assert not np.array_equal(np.asarray(a["layers"]["w_up"]["w_q"]),
                              np.asarray(b["layers"]["w_up"]["w_q"]))
