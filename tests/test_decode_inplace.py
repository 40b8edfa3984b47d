"""The contiguous KV cache is updated in place during decode.

``decode_dense`` carries the stacked (L, B, S, K, D) cache through its layer
scan and writes only each layer's new token. Pinned here:

* bit-identity with the per-layer form it replaced (each layer's slice cut
  out as scan xs, written, and rebuilt as scan ys), kept below as the
  reference, for a shared scalar ``pos`` and per-slot ``pos``, including a
  slot at the last position and slots past it (both writes clamp there);
* the contiguous and paged-view engines still serve the same greedy tokens
  (the view path runs this same program over gathered views);
* structure: the compiled decode segment copies no whole cache leaf inside
  its loop (the per-layer form copied the stacked K and V into the loop
  state every step, and rebuilt them from its ys);
* warm-up runs the decode segment against the live state and leaves it as
  it was: serving after ``warmup()`` gives what a cold engine gives;
* through the real segment, the length-aware decode kernel (interpret mode
  on the CPU) serves the tokens the XLA decode serves, on phi3- and
  Yi-shaped models, and the engine's read counters count the positions of
  each live slot's blocks; a speculating engine keeps the XLA decode.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.registry import ARCHS
from repro.models import build_model
from repro.models import transformer as T
from repro.models import layers as L

# every test here builds and decodes real JAX models
pytestmark = pytest.mark.slow
from repro.serving.engine import Request, ServingEngine  # noqa: E402

ARCH_IDS = ["phi3-mini-3.8b", "llama3.2-1b"]
# reduced phi3 (MHA, G 1) and Yi (GQA, G 2)
KERNEL_ARCH_IDS = ["phi3-mini-3.8b", "yi-9b"]
_BUILT = {}


def _build(arch):
    if arch not in _BUILT:
        cfg = ARCHS[arch].reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _BUILT[arch] = (cfg, model, params)
    return _BUILT[arch]


def _decode_dense_xs_ys(cfg, params, cache, token, pos):
    """The per-layer decode the carried form replaced: each layer's
    (B, S, K, D) slice enters the scan as xs, takes its token through the
    clamping per-layer write, and leaves as ys."""
    x = L.embed_tokens(token, params["embed"], jnp.dtype(cfg.dtype))

    def body(carry, xs):
        blk, kc, vc = xs
        out, kc, vc = T._decode_block(carry, blk, kc, vc, pos, cfg)
        return out, (kc, vc)

    x, (ks, vs) = lax.scan(body, x, (params["layers"], cache["k"],
                                     cache["v"]))
    x = L.rmsnorm(x, params["ln_f"])
    return L.lm_logits(x, params["head"]), {"k": ks, "v": vs}


# per step: the scalar pos, or each slot's pos. The last two slots sit at
# and past the end of a 16-position cache, so their writes clamp.
POS_STEPS = {
    "scalar": [3, 4, 15, 19],
    "per_slot": [[0, 5, 15, 16], [1, 6, 15, 17], [2, 7, 15, 20],
                 [3, 8, 15, 40]],
}


@pytest.mark.parametrize("pos_kind", sorted(POS_STEPS))
@pytest.mark.parametrize("arch", ARCH_IDS)
def test_in_place_decode_bit_identical_to_per_layer_slices(arch, pos_kind):
    cfg, model, params = _build(arch)
    B, S = 4, 16
    shapes = model.cache_shapes(B, S)
    rng = jax.random.PRNGKey(7)
    # random (not zero) cache contents, so a write to the wrong place or
    # a lost one shows in the cache and in attention
    cache = {name: jax.random.normal(jax.random.fold_in(rng, i), s.shape,
                                     s.dtype)
             for i, (name, s) in enumerate(sorted(shapes.items()))}
    new = jax.jit(model.decode)
    ref = jax.jit(lambda p, c, t, pos: _decode_dense_xs_ys(cfg, p, c, t,
                                                           pos))
    c_new, c_ref = cache, cache
    toks = np.random.default_rng(3).integers(
        0, cfg.vocab, size=(len(POS_STEPS[pos_kind]), B, 1)).astype(np.int32)
    for step, pos in enumerate(POS_STEPS[pos_kind]):
        pos = np.asarray(pos, np.int32)
        lg_new, c_new = new(params, c_new, toks[step], pos)
        lg_ref, c_ref = ref(params, c_ref, toks[step], pos)
        np.testing.assert_array_equal(np.asarray(lg_new), np.asarray(lg_ref),
                                      err_msg=f"logits, step {step}")
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(c_new[name]), np.asarray(c_ref[name]),
                err_msg=f"cache {name}, step {step}")
    # the steps did write: the cache moved away from where it started
    assert not np.array_equal(np.asarray(c_new["k"]), np.asarray(cache["k"]))


def _stream(cfg, seed=5, n=7):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(3, 20))
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(2, 12)))
            for i in range(n)]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_contiguous_engine_matches_paged_view_engine(arch):
    cfg, model, params = _build(arch)
    kw = dict(max_batch=3, max_len=64, decode_block=4, min_bucket=4)
    cont = ServingEngine(model, params, **kw)
    view = ServingEngine(model, params, page_size=8, **kw)
    assert not cont._paged and view._view_decode
    r_cont, r_view = _stream(cfg), _stream(cfg)
    cont.serve(r_cont)
    view.serve(r_view)
    for a, b in zip(r_cont, r_view):
        assert len(a.tokens) == a.max_new_tokens
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid={a.rid}")


# ---------------------------------------------------------------------------
# structure of the compiled decode segment

_HLO_TYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def _computations(hlo: str):
    """{computation name: its instruction lines}."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split("(")[0].split()[-1].lstrip("%")
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


_CALLED = re.compile(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _reachable(comps, roots):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            todo += _CALLED.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _while_bodies(comps):
    return [m for lines in comps.values() for line in lines
            if " while(" in line
            for m in re.findall(r"body=%?([\w.\-]+)", line)]


def _leaf_copies(comps, names, leaf):
    """Copy instructions in ``names`` whose result is shaped like ``leaf``."""
    shape = "%s[%s]" % (_HLO_TYPES[str(leaf.dtype)],
                        ",".join(map(str, leaf.shape)))
    pat = re.compile(r"=\s*%s\S*\s+copy(?:-start)?\(" % re.escape(shape))
    return [line.strip() for c in names for line in comps[c]
            if pat.search(line)]


def _decode_args(eng):
    R = max(eng.stage_slots, 1)
    return [eng.params, eng._cache, eng._tok, eng._pos,
            jnp.zeros((eng.max_batch,), jnp.int32), eng._plen, eng._pbuf,
            np.zeros((R, eng.max_len), np.int32), np.zeros((R,), np.int32),
            np.zeros((R,), np.int32), np.int32(0)]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_segment_copies_no_whole_cache(arch):
    _cfg, model, params = _build(arch)
    eng = ServingEngine(model, params, max_batch=4, max_len=64)
    compiled = eng._get_decode().lower(*_decode_args(eng)).compile()
    comps = _computations(compiled.as_text())
    bodies = _while_bodies(comps)
    assert bodies, "the decode segment has no while loop"
    leaf = eng._cache["k"]
    in_loop = _leaf_copies(comps, _reachable(comps, bodies), leaf)
    assert in_loop == [], in_loop


@pytest.mark.parametrize("page_size", [None, 8], ids=["contiguous", "view"])
def test_serving_after_warmup_matches_cold_engine(page_size):
    _cfg, model, params = _build("llama3.2-1b")
    kw = dict(max_batch=3, max_len=64, decode_block=4, min_bucket=4,
              page_size=page_size)
    warm = ServingEngine(model, params, **kw)
    warm.warmup(prompt_lens=[6])
    prompt = np.arange(6, dtype=np.int32) % 11
    got = warm.serve([Request(rid=0, prompt=prompt, max_new_tokens=9)])[0]
    cold = ServingEngine(model, params, **kw)
    want = cold.serve([Request(rid=0, prompt=prompt, max_new_tokens=9)])[0]
    np.testing.assert_array_equal(np.asarray(got.tokens),
                                  np.asarray(want.tokens))
    assert warm.stats["decode_traces"] == 1


def _kv_blocks_read(reqs, block):
    """Positions the length-aware decode fetches over a whole serve with
    prefill admission: request ``r`` decodes ``max_new - 1`` steps after
    its prefill, attending ``len(prompt) + 1`` positions at the first,
    one more at each next, in blocks of ``block``."""
    n = 0
    for r in reqs:
        vlen = len(r.prompt) + 1 + np.arange(r.max_new_tokens - 1)
        n += int((-(-vlen // block) * block).sum())
    return n


@pytest.mark.parametrize("arch", KERNEL_ARCH_IDS)
def test_length_aware_decode_engine_matches_xla_engine(arch):
    import dataclasses
    cfg = ARCHS[arch].reduced()
    kw = dict(max_batch=3, max_len=64, decode_block=4, min_bucket=4)
    engines = {}
    for impl in ("xla", "pallas_interpret"):
        model = build_model(dataclasses.replace(cfg, attention_impl=impl))
        params = model.init(jax.random.PRNGKey(0))
        eng = ServingEngine(model, params, **kw)
        reqs = _stream(cfg)
        eng.serve(reqs)
        engines[impl] = (eng, reqs)
    (x_eng, x_reqs), (k_eng, k_reqs) = engines["xla"], \
        engines["pallas_interpret"]
    for a, b in zip(x_reqs, k_reqs):
        assert len(a.tokens) == a.max_new_tokens
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid={a.rid}")
    # the kernel reads each live slot's blocks below its length, in
    # blocks of 16 here (a quarter of the 64 positions); XLA reads all
    full = k_eng.stats["decode_steps"] * 3 * 64
    assert k_eng.stats["decode_kv_positions_full"] == full
    assert k_eng.stats["decode_kv_positions_read"] == \
        _kv_blocks_read(k_reqs, 16) < full
    assert x_eng.stats["decode_kv_positions_read"] == \
        x_eng.stats["decode_kv_positions_full"] == \
        x_eng.stats["decode_steps"] * 3 * 64


def test_speculating_engine_keeps_xla_decode():
    _cfg, model, params = _build("llama3.2-1b")
    eng = ServingEngine(model, params, max_batch=3, max_len=64,
                        decode_block=4, min_bucket=4,
                        speculate=(model, params), spec_k=2)
    assert not eng._live_decode
    eng.serve(_stream(_cfg))
    assert eng.stats["decode_kv_positions_read"] == \
        eng.stats["decode_kv_positions_full"] > 0


def test_length_aware_decode_counts_in_segment_seats():
    """Requests seated inside the segment (``stage_slots``) feed their
    prompt through the decode loop from position 0: through the kernel they
    decode what the XLA engine decodes, and each such seat reads the blocks
    of positions 1 .. len(prompt) on top of what a prefilled request
    reads."""
    import dataclasses
    cfg = ARCHS["phi3-mini-3.8b"].reduced()
    kw = dict(max_batch=2, max_len=64, decode_block=8, min_bucket=4,
              stage_slots=4)
    plen = 6
    max_new = np.random.default_rng(11).integers(2, 9, size=6)
    runs = {}
    for impl in ("xla", "pallas_interpret"):
        model = build_model(dataclasses.replace(cfg, attention_impl=impl))
        eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), **kw)
        reqs = [Request(rid=i, prompt=np.arange(i, i + plen, dtype=np.int32),
                        max_new_tokens=int(n))
                for i, n in enumerate(max_new)]
        eng.serve(reqs)
        runs[impl] = (eng, reqs)
    (_, x_reqs), (k_eng, k_reqs) = runs["xla"], runs["pallas_interpret"]
    for a, b in zip(x_reqs, k_reqs):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens),
                                      err_msg=f"rid={a.rid}")
    seats = k_eng.stats["inseg_admissions"]
    assert seats > 0
    # positions 1 .. 6 fall in the first block of 16
    assert k_eng.stats["decode_kv_positions_read"] == \
        _kv_blocks_read(k_reqs, 16) + seats * plen * 16
