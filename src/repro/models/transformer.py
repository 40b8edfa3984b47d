"""Dense decoder-only transformer (llama family), plus the VLM (cross-attn
image layers) and audio (enc-dec) backbones which reuse the same blocks.

All stacks are scanned over layers (params stacked on a leading L dim) so the
HLO stays compact for 100-layer configs; ``cfg.remat`` wraps the scan body in
``jax.checkpoint`` for training.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import kvcache as KV

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# parameter init


def _stacked_attn_init(rng, n: int, cfg: ArchConfig, dtype,
                       kv_heads: Optional[int] = None) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    nk = kv_heads if kv_heads is not None else cfg.n_kv_heads
    g = cfg.n_heads // nk
    ks = jax.random.split(rng, 4)
    return {
        "wq": L.dense_init(ks[0], (n, d, nk, g, hd), dtype, in_axis=1),
        "wk": L.dense_init(ks[1], (n, d, nk, hd), dtype, in_axis=1),
        "wv": L.dense_init(ks[2], (n, d, nk, hd), dtype, in_axis=1),
        "wo": L.dense_init(ks[3], (n, nk, g, hd, d), dtype, in_axis=-1),
    }


def _stacked_mlp_init(rng, n: int, cfg: ArchConfig, dtype) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(rng, 3)
    return {
        "w_gate": L.dense_init(ks[0], (n, d, f), dtype, in_axis=1),
        "w_up": L.dense_init(ks[1], (n, d, f), dtype, in_axis=1),
        "w_down": L.dense_init(ks[2], (n, f, d), dtype, in_axis=1),
    }


def _block_stack_init(rng, n: int, cfg: ArchConfig, dtype) -> Params:
    ka, km = jax.random.split(rng)
    return {
        "attn": _stacked_attn_init(ka, n, cfg, dtype),
        "mlp": _stacked_mlp_init(km, n, cfg, dtype),
        "ln1": jnp.zeros((n, cfg.d_model), dtype),
        "ln2": jnp.zeros((n, cfg.d_model), dtype),
    }


def init_dense(cfg: ArchConfig, rng) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kl, kh = jax.random.split(rng, 3)
    return {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), dtype),
        "layers": _block_stack_init(kl, cfg.n_layers, cfg, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "head": L.embed_init(kh, (cfg.vocab, cfg.d_model), dtype),
    }


def init_vlm(cfg: ArchConfig, rng) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    n_cross = cfg.n_layers // cfg.cross_attn_every
    n_self = cfg.n_layers - n_cross
    ke, ks, kc, kh = jax.random.split(rng, 4)
    p = {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), dtype),
        "layers": _block_stack_init(ks, n_self, cfg, dtype),
        "cross_layers": _block_stack_init(kc, n_cross, cfg, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "head": L.embed_init(kh, (cfg.vocab, cfg.d_model), dtype),
    }
    return p


def init_audio(cfg: ArchConfig, rng) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    ke, kenc, kdec, kx, kh = jax.random.split(rng, 5)
    return {
        "embed": L.embed_init(ke, (cfg.vocab, cfg.d_model), dtype),
        "encoder": _block_stack_init(kenc, cfg.n_encoder_layers, cfg, dtype),
        "decoder": _block_stack_init(kdec, cfg.n_layers, cfg, dtype),
        "cross": _block_stack_init(kx, cfg.n_layers, cfg, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "head": L.embed_init(kh, (cfg.vocab, cfg.d_model), dtype),
    }


# ---------------------------------------------------------------------------
# block bodies


def _qimpl(cfg: ArchConfig) -> str:
    """qeinsum impl for the config's quantize mode (no-op on fp weights)."""
    return {"none": "xla", "int8": "xla", "int8_pallas": "pallas",
            "int8_pallas_interpret": "pallas_interpret"}[cfg.quantize]


def _self_block(x, blk, cfg: ArchConfig, *, causal=True, positions=None,
                rope=True, kv_valid_len=None):
    qi = _qimpl(cfg)
    h = L.rmsnorm(x, blk["ln1"])
    q, k, v = L.attn_qkv(h, blk["attn"], qimpl=qi)
    if positions is None:
        positions = jnp.arange(x.shape[1])[None, :]
    if rope:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    o = L.attention_core(q, k, v, causal=causal, kv_valid_len=kv_valid_len,
                         impl=cfg.attention_impl)
    x = x + L.attn_out(o, blk["attn"], qimpl=qi)
    x = x + L.swiglu(L.rmsnorm(x, blk["ln2"]), blk["mlp"], qimpl=qi)
    return L.constrain_residual(x)


def _cross_block(x, blk, ctx, cfg: ArchConfig, valid_len=None):
    """Cross-attention block: queries from x, KV from ctx (no RoPE/causality).

    ``valid_len``: optional scalar or (B,) true context lengths; padded
    context rows mask out of the softmax (exact zeros)."""
    h = L.rmsnorm(x, blk["ln1"])
    q = jnp.einsum("bsd,dkgh->bskgh", h, blk["attn"]["wq"])
    k = jnp.einsum("btd,dkh->btkh", ctx, blk["attn"]["wk"])
    v = jnp.einsum("btd,dkh->btkh", ctx, blk["attn"]["wv"])
    o = L.attention_core(q, k, v, causal=False, kv_valid_len=valid_len,
                         impl=cfg.attention_impl)
    x = x + L.attn_out(o, blk["attn"])
    x = x + L.swiglu(L.rmsnorm(x, blk["ln2"]), blk["mlp"])
    return L.constrain_residual(x)


def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _scan_blocks(x, stack: Params, cfg: ArchConfig, *, causal=True,
                 positions=None, rope=True, kv_valid_len=None):
    def body(carry, blk):
        return _self_block(carry, blk, cfg, causal=causal,
                           positions=positions, rope=rope,
                           kv_valid_len=kv_valid_len), None
    x, _ = lax.scan(_maybe_remat(body, cfg), x, stack)
    return x


# ---------------------------------------------------------------------------
# dense: train forward / prefill / decode


def forward_dense(cfg: ArchConfig, params: Params, tokens: jax.Array) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(tokens, params["embed"], dtype)
    x = _scan_blocks(x, params["layers"], cfg)
    x = L.rmsnorm(x, params["ln_f"])
    return L.lm_logits(x, params["head"])


def _prefill_scan(x, stack, cfg: ArchConfig, positions):
    """Forward over layers, emitting per-layer (k, v) as scan ys."""
    qi = _qimpl(cfg)

    def body(carry, blk):
        h = L.rmsnorm(carry, blk["ln1"])
        q, k, v = L.attn_qkv(h, blk["attn"], qimpl=qi)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.attention_core(q, k, v, causal=True, impl=cfg.attention_impl)
        out = carry + L.attn_out(o, blk["attn"], qimpl=qi)
        out = out + L.swiglu(L.rmsnorm(out, blk["ln2"]), blk["mlp"],
                             qimpl=qi)
        return L.constrain_residual(out), (k, v)
    x, (ks, vs) = lax.scan(_maybe_remat(body, cfg), x, stack)
    return x, ks, vs


def prefill_dense(cfg: ArchConfig, params: Params, tokens: jax.Array,
                  length: Optional[jax.Array] = None):
    """``length``: optional (B,) valid prefix lengths for right-padded
    prompts; next-token logits are read at position length-1 (causal
    attention keeps valid positions independent of right padding)."""
    dtype = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    x = L.embed_tokens(tokens, params["embed"], dtype)
    x, ks, vs = _prefill_scan(x, params["layers"], cfg, positions)
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(L.select_last(x, length), params["head"])
    return logits, {"k": ks, "v": vs}


def decode_positions(pos, batch: int) -> jax.Array:
    """(B, 1) RoPE positions from a shared scalar or per-sequence (B,) pos."""
    if jnp.ndim(pos) == 0:
        return jnp.full((batch, 1), pos)
    return jnp.reshape(pos, (batch, 1))


def _decode_qkv(x, blk, pos, cfg: ArchConfig):
    """Norm, q/k/v projections and RoPE of one decode step. x: (B,1,d)."""
    h = L.rmsnorm(x, blk["ln1"])
    q, k, v = L.attn_qkv(h, blk["attn"], qimpl=_qimpl(cfg))
    positions = decode_positions(pos, x.shape[0])
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _decode_attend(q, kc, vc, pos, cfg: ArchConfig):
    """Attention of the step's queries over a contiguous (B,Smax,K,D) slot
    row, masked to each slot's positions ``<= pos``."""
    return L.attention_core(q, kc, vc, causal=False, kv_valid_len=pos + 1,
                            impl=cfg.attention_impl)


def _decode_out(x, o, blk, cfg: ArchConfig):
    """Output projection and MLP of one decode step, both residual."""
    qi = _qimpl(cfg)
    x = x + L.attn_out(o, blk["attn"], qimpl=qi)
    return x + L.swiglu(L.rmsnorm(x, blk["ln2"]), blk["mlp"], qimpl=qi)


def _decode_block(x, blk, kc, vc, pos, cfg: ArchConfig, bt=None):
    """One decode step through one block. x: (B,1,d).

    ``pos`` is a shared scalar or a per-sequence (B,) vector of positions.
    With ``bt=None`` kc/vc are contiguous (B,Smax,K,D) slot rows; with a
    (B, P) block table they are shared (n_pages, page_size, K, D) pools and
    the write/attend both route through the slot's block table.
    """
    q, k, v = _decode_qkv(x, blk, pos, cfg)
    if bt is None:
        kc, vc = KV.update_layer_cache(kc, vc, k, v, pos)
        o = _decode_attend(q, kc, vc, pos, cfg)
    else:
        o, kc, vc = L.paged_update_attend(q, k, v, kc, vc, bt, pos,
                                          impl=cfg.attention_impl)
    return _decode_out(x, o, blk, cfg), kc, vc


def decode_dense(cfg: ArchConfig, params: Params, cache, token: jax.Array,
                 pos, plan: Optional[Any] = None) -> Tuple[jax.Array, Any]:
    """serve_step: one new token against the cache. token: (B,1) int32.

    ``cache`` may carry a ``"bt"`` block table, in which case its k/v
    leaves are shared page pools (see ``repro.models.kvcache``), scanned
    layer by layer as xs/ys. A contiguous (L,B,Smax,K,D) cache instead
    rides in the scan carry: layer ``l`` writes its token's rows in place,
    then attends over layer ``l`` of the carry, so no step copies a layer
    slice, or the stack, whole.

    ``plan``: optional ``kernels.decode_attention.stacked_decode_plan`` of
    each slot's valid length for this step (a serving engine's: ``pos + 1``
    for a live slot, 0 for a dead one). With it, a contiguous decode
    attends each slot over its valid length through ``L.attention_core``'s
    stacked-carry route (the length-aware kernel on a TPU), and a dead
    slot's logits are meaningless. Without it every slot attends over
    ``pos + 1`` through XLA, which the speculative verifier's bit-identity
    rests on."""
    dtype = jnp.dtype(cfg.dtype)
    bt = cache.get("bt")
    x = L.embed_tokens(token, params["embed"], dtype)

    if bt is None:
        def body(carry, xs):
            h, ks, vs = carry
            blk, layer = xs
            q, k, v = _decode_qkv(h, blk, pos, cfg)
            ks, vs = KV.update_stacked_layer_cache(ks, vs, k, v, layer, pos)
            if plan is None:
                o = _decode_attend(
                    q, lax.dynamic_index_in_dim(ks, layer, keepdims=False),
                    lax.dynamic_index_in_dim(vs, layer, keepdims=False),
                    pos, cfg)
            else:
                o = L.attention_core(q, ks, vs, causal=False, layer=layer,
                                     kv_valid_len=plan,
                                     impl=cfg.attention_impl)
            return (_decode_out(h, o, blk, cfg), ks, vs), None

        n_layers = cache["k"].shape[0]
        (x, ks, vs), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    else:
        def body(carry, xs):
            blk, kc, vc = xs
            out, kc, vc = _decode_block(carry, blk, kc, vc, pos, cfg, bt=bt)
            return out, (kc, vc)

        x, (ks, vs) = lax.scan(body, x,
                               (params["layers"], cache["k"], cache["v"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(x, params["head"])
    out_cache = {"k": ks, "v": vs}
    if bt is not None:
        out_cache["bt"] = bt
    return logits, out_cache


def decode_window_dense(cfg: ArchConfig, params: Params, cache,
                        tokens: jax.Array, pos) -> Tuple[jax.Array, Any]:
    """Teacher-forced decode window: feed S tokens per slot in ONE forward.

    tokens: (B, S) int32, pos: (B,) — window token ``s`` of slot ``b``
    sits at absolute position ``pos[b] + s``. KV for the whole window is
    written first (the standard contiguous vector-pos write, or the paged
    window scatter), then attention masks each window token to positions
    ``<= pos[b] + s``. Returns ((B, S, vocab) logits, cache).

    This is the speculative-decode verifier: row ``s`` of the logits is
    exactly what ``decode_dense`` would emit after feeding tokens
    ``0..s`` sequentially, so greedy acceptance against these rows keeps
    spec-decode bit-identical to plain decode. Callers must guarantee
    ``pos[b] + S <= S_max`` for live slots (the contiguous window write
    clamps, which would silently rewrite earlier positions) and that
    paged slots have pages attached through ``pos[b] + S - 1``.
    """
    dtype = jnp.dtype(cfg.dtype)
    bt = cache.get("bt")
    B, S = tokens.shape
    qi = _qimpl(cfg)
    positions = jnp.reshape(pos, (B, 1)) + jnp.arange(S)[None, :]
    x = L.embed_tokens(tokens, params["embed"], dtype)

    def body(carry, xs):
        blk, kc, vc = xs
        h = L.rmsnorm(carry, blk["ln1"])
        q, k, v = L.attn_qkv(h, blk["attn"], qimpl=qi)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        if bt is None:
            kc, vc = KV.update_layer_cache(kc, vc, k, v, pos)
            o = L.window_attention_core(q, kc, vc, pos)
        else:
            kc, vc = KV.paged_update_window(kc, vc, k, v, bt, pos)
            o = L.window_attention_core(q, KV.gather_block_kv(kc, bt),
                                        KV.gather_block_kv(vc, bt), pos)
        out = carry + L.attn_out(o, blk["attn"], qimpl=qi)
        out = out + L.swiglu(L.rmsnorm(out, blk["ln2"]), blk["mlp"],
                             qimpl=qi)
        return out, (kc, vc)

    x, (ks, vs) = lax.scan(body, x, (params["layers"], cache["k"],
                                     cache["v"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(x, params["head"])
    out_cache = {"k": ks, "v": vs}
    if bt is not None:
        out_cache["bt"] = bt
    return logits, out_cache


# ---------------------------------------------------------------------------
# VLM: self stack with interleaved cross-attention groups


def _vlm_scan(x, params, cfg: ArchConfig, image_embeds, decode_state=None,
              pos=None):
    """Grouped scan: (cross_every - 1) self layers then 1 cross layer.

    decode_state: None for full-seq forward; else dict with self k/v caches
    stacked (n_self, ...) and cross k/v stacked (n_cross, ...).
    """
    n_cross = cfg.n_layers // cfg.cross_attn_every
    n_self_per = cfg.cross_attn_every - 1

    def regroup(stack, n_groups, per):
        return jax.tree.map(
            lambda a: a.reshape((n_groups, per) + a.shape[1:]), stack)

    self_grouped = regroup(params["layers"], n_cross, n_self_per)

    def group_body(carry, xs):
        self_blks, cross_blk = xs
        def inner(c, blk):
            return _self_block(c, blk, cfg), None
        carry, _ = lax.scan(_maybe_remat(inner, cfg), carry, self_blks)
        # remat the cross block itself (group-level remat would recompute
        # the whole 9-layer inner scan a second time: §Perf B-iter1)
        cross = _maybe_remat(
            lambda c, blk: _cross_block(c, blk, image_embeds, cfg), cfg)
        carry = cross(carry, cross_blk)
        return carry, None

    x, _ = lax.scan(group_body, x,
                    (self_grouped, params["cross_layers"]))
    return x


def forward_vlm(cfg: ArchConfig, params: Params, tokens: jax.Array,
                image_embeds: jax.Array) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_tokens(tokens, params["embed"], dtype)
    x = _vlm_scan(x, params, cfg, image_embeds.astype(dtype))
    x = L.rmsnorm(x, params["ln_f"])
    return L.lm_logits(x, params["head"])


def prefill_vlm(cfg: ArchConfig, params: Params, tokens: jax.Array,
                image_embeds: jax.Array,
                length: Optional[jax.Array] = None):
    """Prefill emitting self-attn KV per self layer + cross KV per cross layer."""
    dtype = jnp.dtype(cfg.dtype)
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    n_cross = cfg.n_layers // cfg.cross_attn_every
    n_self_per = cfg.cross_attn_every - 1
    img = image_embeds.astype(dtype)

    self_grouped = jax.tree.map(
        lambda a: a.reshape((n_cross, n_self_per) + a.shape[1:]),
        params["layers"])

    def group_body(carry, xs):
        self_blks, cross_blk = xs
        def inner(c, blk):
            h = L.rmsnorm(c, blk["ln1"])
            q, k, v = L.attn_qkv(h, blk["attn"])
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            o = L.attention_core(q, k, v, causal=True, impl=cfg.attention_impl)
            out = c + L.attn_out(o, blk["attn"])
            out = out + L.swiglu(L.rmsnorm(out, blk["ln2"]), blk["mlp"])
            return L.constrain_residual(out), (k, v)
        carry, (ks, vs) = lax.scan(_maybe_remat(inner, cfg), carry, self_blks)
        xk = jnp.einsum("btd,dkh->btkh", img, cross_blk["attn"]["wk"])
        xv = jnp.einsum("btd,dkh->btkh", img, cross_blk["attn"]["wv"])
        carry = _cross_block(carry, cross_blk, img, cfg)
        return carry, (ks, vs, xk, xv)

    x = L.embed_tokens(tokens, params["embed"], dtype)
    x, (ks, vs, xks, xvs) = lax.scan(_maybe_remat(group_body, cfg), x,
                                     (self_grouped, params["cross_layers"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(L.select_last(x, length), params["head"])
    cache = {"k": ks, "v": vs, "xk": xks, "xv": xvs}
    return logits, cache


def decode_vlm(cfg: ArchConfig, params: Params, cache, token: jax.Array, pos):
    dtype = jnp.dtype(cfg.dtype)
    n_cross = cfg.n_layers // cfg.cross_attn_every
    n_self_per = cfg.cross_attn_every - 1
    bt = cache.get("bt")
    x = L.embed_tokens(token, params["embed"], dtype)

    self_grouped = jax.tree.map(
        lambda a: a.reshape((n_cross, n_self_per) + a.shape[1:]),
        params["layers"])

    def group_body(carry, xs):
        self_blks, cross_blk, kc, vc, xk, xv = xs

        def inner(c, layer_xs):
            blk, k1, v1 = layer_xs
            out, k1, v1 = _decode_block(c, blk, k1, v1, pos, cfg, bt=bt)
            return out, (k1, v1)

        carry, (kc, vc) = lax.scan(inner, carry, (self_blks, kc, vc))
        # cross attention against the cached image KV
        h = L.rmsnorm(carry, cross_blk["ln1"])
        q = jnp.einsum("bsd,dkgh->bskgh", h, cross_blk["attn"]["wq"])
        o = L.attention_core(q, xk, xv, causal=False, impl=cfg.attention_impl)
        carry = carry + L.attn_out(o, cross_blk["attn"])
        carry = carry + L.swiglu(L.rmsnorm(carry, cross_blk["ln2"]),
                                 cross_blk["mlp"])
        return carry, (kc, vc)

    x, (ks, vs) = lax.scan(group_body, x,
                           (self_grouped, params["cross_layers"],
                            cache["k"], cache["v"], cache["xk"], cache["xv"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(x, params["head"])
    out_cache = {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"]}
    if bt is not None:
        out_cache["bt"] = bt
    return logits, out_cache


# ---------------------------------------------------------------------------
# audio (enc-dec): stub frame embeddings in, decoder tokens out


def _encode(cfg: ArchConfig, params: Params, frames: jax.Array,
            valid_len=None) -> jax.Array:
    """frames: (B, T, d_model) precomputed stub embeddings.

    ``valid_len``: optional (B,) true frame counts for right-padded frame
    batches; padded rows are masked out of the (bidirectional) encoder
    self-attention so valid encoder outputs are independent of padding."""
    x = frames.astype(jnp.dtype(cfg.dtype))
    return _scan_blocks(x, params["encoder"], cfg, causal=False,
                        kv_valid_len=valid_len)


def forward_audio(cfg: ArchConfig, params: Params, tokens: jax.Array,
                  frames: jax.Array) -> jax.Array:
    dtype = jnp.dtype(cfg.dtype)
    enc = _encode(cfg, params, frames)
    x = L.embed_tokens(tokens, params["embed"], dtype)

    def body(carry, xs):
        dec_blk, cross_blk = xs
        carry = _self_block(carry, dec_blk, cfg, causal=True)
        carry = _cross_block(carry, cross_blk, enc, cfg)
        return carry, None

    x, _ = lax.scan(_maybe_remat(body, cfg), x,
                    (params["decoder"], params["cross"]))
    x = L.rmsnorm(x, params["ln_f"])
    return L.lm_logits(x, params["head"])


def prefill_audio(cfg: ArchConfig, params: Params, tokens: jax.Array,
                  frames: jax.Array, length: Optional[jax.Array] = None):
    """``length``: optional (B,) valid prefix lengths, shared by the token
    prompt and the frame stream. Encoder self-attention and decoder cross-
    attention both mask by the true encoder length, so padded encoder rows
    contribute exact zeros — outputs no longer depend on the padded width,
    and the paged cache's dropped writes on padding rows are unobservable.
    The true length rides in the cache (``enc_len``) for decode."""
    dtype = jnp.dtype(cfg.dtype)
    enc = _encode(cfg, params, frames, valid_len=length)
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    enc_len = length if length is not None \
        else jnp.full((B,), frames.shape[1], jnp.int32)
    enc_len = enc_len.astype(jnp.int32)
    x = L.embed_tokens(tokens, params["embed"], dtype)

    def body(carry, xs):
        dec_blk, cross_blk = xs
        h = L.rmsnorm(carry, dec_blk["ln1"])
        q, k, v = L.attn_qkv(h, dec_blk["attn"])
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.attention_core(q, k, v, causal=True, impl=cfg.attention_impl)
        carry = carry + L.attn_out(o, dec_blk["attn"])
        carry = L.constrain_residual(
            carry + L.swiglu(L.rmsnorm(carry, dec_blk["ln2"]),
                             dec_blk["mlp"]))
        xk = jnp.einsum("btd,dkh->btkh", enc, cross_blk["attn"]["wk"])
        xv = jnp.einsum("btd,dkh->btkh", enc, cross_blk["attn"]["wv"])
        carry = _cross_block(carry, cross_blk, enc, cfg, valid_len=length)
        return carry, (k, v, xk, xv)

    x, (ks, vs, xks, xvs) = lax.scan(body, x,
                                     (params["decoder"], params["cross"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(L.select_last(x, length), params["head"])
    return logits, {"k": ks, "v": vs, "xk": xks, "xv": xvs,
                    "enc_len": enc_len}


def decode_audio(cfg: ArchConfig, params: Params, cache, token: jax.Array, pos):
    dtype = jnp.dtype(cfg.dtype)
    bt = cache.get("bt")
    enc_len = cache["enc_len"]
    x = L.embed_tokens(token, params["embed"], dtype)

    def body(carry, xs):
        dec_blk, cross_blk, kc, vc, xk, xv = xs
        carry, kc, vc = _decode_block(carry, dec_blk, kc, vc, pos, cfg,
                                      bt=bt)
        h = L.rmsnorm(carry, cross_blk["ln1"])
        q = jnp.einsum("bsd,dkgh->bskgh", h, cross_blk["attn"]["wq"])
        if bt is None:
            o = L.attention_core(q, xk, xv, causal=False,
                                 kv_valid_len=enc_len,
                                 impl=cfg.attention_impl)
        else:
            o = L.paged_attention_core(q, xk, xv, bt, kv_valid_len=enc_len,
                                       impl=cfg.attention_impl)
        carry = carry + L.attn_out(o, cross_blk["attn"])
        carry = carry + L.swiglu(L.rmsnorm(carry, cross_blk["ln2"]),
                                 cross_blk["mlp"])
        return carry, (kc, vc)

    x, (ks, vs) = lax.scan(body, x, (params["decoder"], params["cross"],
                                     cache["k"], cache["v"],
                                     cache["xk"], cache["xv"]))
    x = L.rmsnorm(x, params["ln_f"])
    logits = L.lm_logits(x, params["head"])
    out_cache = {"k": ks, "v": vs, "xk": cache["xk"], "xv": cache["xv"],
                 "enc_len": enc_len}
    if bt is not None:
        out_cache["bt"] = bt
    return logits, out_cache
