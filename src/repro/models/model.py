"""Unified model interface over the 10 assigned architecture families.

``build_model(cfg, parallel=None)`` returns a ``Model`` with:
  * ``init(rng) -> params``
  * ``forward(params, batch) -> logits``          (full-sequence, causal)
  * ``loss(params, batch) -> scalar``             (mean token cross-entropy)
  * ``prefill(params, batch) -> (logits, cache)``
  * ``decode(params, cache, token, pos) -> (logits, cache)``  (serve_step)
  * ``cache_shapes(batch, max_len) -> pytree of ShapeDtypeStruct``

All functions are jit/pjit-compatible and usable under ``jax.eval_shape``.

Serving extensions (used by the continuous-batching engine):
  * ``batch`` may carry ``"length"``, a (B,) int array of valid prefix
    lengths for prompts right-padded to a shared bucket. Prefill then reads
    the next-token logits at position length-1 (still returning (B, 1, V))
    and — for the recurrent families — masks the recurrence so padded
    positions leave the carried state untouched.
  * ``decode``'s ``pos`` may be a (B,) vector of per-sequence positions
    instead of a shared scalar (each batch slot at its own decode offset).
  * ``decode``'s cache may carry a ``"bt"`` block table (B, P), in which
    case the attention k/v leaves are shared page pools
    (``repro.models.kvcache`` paged layout) and writes/reads route through
    the slot's block table; recurrent O(1) state leaves stay slot-indexed.
    Supported by the dense/moe/hybrid/vlm/audio decode paths (audio
    carries its true encoder length per slot as an ``enc_len`` cache leaf
    and masks cross-attention by it).
  * the dense ``decode`` takes an optional ``plan``, the step's
    ``stacked_decode_plan`` of each slot's valid length: a contiguous
    cache then attends each slot over its length (a dead slot over none),
    through the length-aware decode kernel on a TPU
    (``transformer.decode_dense``).
  * ``empty_state(batch, max_len)`` returns the decode cache of a
    sequence that has seen no tokens — the slot-reset seam the serving
    engine uses for chunked prefill and in-segment admission (all-zeros
    except xLSTM's -inf stabilizers).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.models import moe as M
from repro.models import ssm as S
from repro.models import xlstm as X

Batch = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable[..., Any]
    forward: Callable[..., jax.Array]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    cache_shapes: Callable[..., Any]
    # empty_state(batch, max_len, **kw) -> concrete cache pytree for a
    # sequence that has seen no tokens: the slot-reset seam the serving
    # engine uses for chunked prefill and in-segment admission. Defaults
    # to all-zeros (valid for attention KV and SSM/conv states); xLSTM
    # overrides it (its sLSTM/mLSTM stabilizers start at -inf, not zero).
    empty_state: Optional[Callable[..., Any]] = None
    # decode_window(params, cache, tokens (B,S), pos (B,)) ->
    # (logits (B,S,V), cache): teacher-forced S-token decode window used
    # by the speculative-decode verifier. Only the dense family wires it
    # (its KV write/attend has an exact batched form); families without
    # it verify sequentially through ``decode``.
    decode_window: Optional[Callable[..., Any]] = None

    def loss(self, params, batch: Batch) -> jax.Array:
        logits = self.forward(params, batch)
        return L.cross_entropy(logits, batch["targets"])


def _zeros_empty_state(cache_shapes: Callable[..., Any]):
    def empty_state(batch: int, max_len: int, **kw):
        shapes = cache_shapes(batch, max_len, **kw)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return empty_state


def _attn_cache_shapes(cfg: ArchConfig, n_layers: int, batch: int,
                       max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    sh = (n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(sh, dtype),
            "v": jax.ShapeDtypeStruct(sh, dtype)}


def build_model(cfg: ArchConfig, parallel=None) -> Model:
    fam = cfg.family

    if fam in ("dense",):
        cs = lambda batch, max_len, **kw: _attn_cache_shapes(  # noqa: E731
            cfg, cfg.n_layers, batch, max_len)
        return Model(
            cfg=cfg,
            init=lambda rng: T.init_dense(cfg, rng),
            forward=lambda p, b: T.forward_dense(cfg, p, b["tokens"]),
            prefill=lambda p, b: T.prefill_dense(cfg, p, b["tokens"],
                                                 length=b.get("length")),
            decode=lambda p, c, t, pos, plan=None: T.decode_dense(
                cfg, p, c, t, pos, plan=plan),
            cache_shapes=cs,
            empty_state=_zeros_empty_state(cs),
            decode_window=lambda p, c, t, pos: T.decode_window_dense(
                cfg, p, c, t, pos),
        )

    if fam == "moe":
        cs = lambda batch, max_len, **kw: _attn_cache_shapes(  # noqa: E731
            cfg, cfg.n_layers, batch, max_len)
        return Model(
            cfg=cfg,
            init=lambda rng: M.init_moe(cfg, rng),
            forward=lambda p, b: M.forward_moe(cfg, p, b["tokens"], parallel),
            prefill=lambda p, b: M.prefill_moe(cfg, p, b["tokens"], parallel,
                                               length=b.get("length")),
            decode=lambda p, c, t, pos: M.decode_moe(cfg, p, c, t, pos,
                                                     parallel),
            cache_shapes=cs,
            empty_state=_zeros_empty_state(cs),
        )

    if fam == "hybrid":
        cs = lambda batch, max_len, **kw: S.zamba_cache_shapes(  # noqa: E731
            cfg, batch, max_len)
        return Model(
            cfg=cfg,
            init=lambda rng: S.init_zamba(cfg, rng),
            forward=lambda p, b: S.forward_zamba(cfg, p, b["tokens"]),
            prefill=lambda p, b: S.prefill_zamba(cfg, p, b["tokens"],
                                                 length=b.get("length")),
            decode=lambda p, c, t, pos: S.decode_zamba(cfg, p, c, t, pos),
            cache_shapes=cs,
            empty_state=_zeros_empty_state(cs),
        )

    if fam == "ssm":
        return Model(
            cfg=cfg,
            init=lambda rng: X.init_xlstm(cfg, rng),
            forward=lambda p, b: X.forward_xlstm(cfg, p, b["tokens"]),
            prefill=lambda p, b: X.prefill_xlstm(cfg, p, b["tokens"],
                                                 length=b.get("length")),
            decode=lambda p, c, t, pos: X.decode_xlstm(cfg, p, c, t, pos),
            cache_shapes=lambda batch, max_len, **kw: X.xlstm_cache_shapes(
                cfg, batch, max_len),
            empty_state=lambda batch, max_len, **kw: X.xlstm_empty_state(
                cfg, batch),
        )

    if fam == "audio":
        def cache_shapes(batch, max_len, enc_len=None, **kw):
            enc_len = enc_len or max_len
            c = _attn_cache_shapes(cfg, cfg.n_layers, batch, max_len)
            xsh = (cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
            dtype = jnp.dtype(cfg.dtype)
            c["xk"] = jax.ShapeDtypeStruct(xsh, dtype)
            c["xv"] = jax.ShapeDtypeStruct(xsh, dtype)
            # per-sequence true encoder length: cross-attention masks
            # padded encoder rows by it (a batch-indexed state leaf, so
            # the serving engine threads it per slot like any O(1) state)
            c["enc_len"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
            return c
        return Model(
            cfg=cfg,
            init=lambda rng: T.init_audio(cfg, rng),
            forward=lambda p, b: T.forward_audio(cfg, p, b["tokens"],
                                                 b["frames"]),
            prefill=lambda p, b: T.prefill_audio(cfg, p, b["tokens"],
                                                 b["frames"],
                                                 length=b.get("length")),
            decode=lambda p, c, t, pos: T.decode_audio(cfg, p, c, t, pos),
            cache_shapes=cache_shapes,
            empty_state=_zeros_empty_state(cache_shapes),
        )

    if fam == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        n_self_per = cfg.cross_attn_every - 1

        def cache_shapes(batch, max_len, **kw):
            dtype = jnp.dtype(cfg.dtype)
            sh = (n_cross, n_self_per, batch, max_len, cfg.n_kv_heads,
                  cfg.head_dim)
            xsh = (n_cross, batch, cfg.n_image_tokens, cfg.n_kv_heads,
                   cfg.head_dim)
            return {"k": jax.ShapeDtypeStruct(sh, dtype),
                    "v": jax.ShapeDtypeStruct(sh, dtype),
                    "xk": jax.ShapeDtypeStruct(xsh, dtype),
                    "xv": jax.ShapeDtypeStruct(xsh, dtype)}
        vlm_cs = cache_shapes
        return Model(
            cfg=cfg,
            init=lambda rng: T.init_vlm(cfg, rng),
            forward=lambda p, b: T.forward_vlm(cfg, p, b["tokens"],
                                               b["image_embeds"]),
            prefill=lambda p, b: T.prefill_vlm(cfg, p, b["tokens"],
                                               b["image_embeds"],
                                               length=b.get("length")),
            decode=lambda p, c, t, pos: T.decode_vlm(cfg, p, c, t, pos),
            cache_shapes=vlm_cs,
            empty_state=_zeros_empty_state(vlm_cs),
        )

    raise ValueError(f"unknown family {fam!r}")


def make_batch(cfg: ArchConfig, rng, batch: int, seq: int,
               with_targets: bool = True) -> Batch:
    """Random batch for smoke tests / examples (concrete arrays)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    b: Batch = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab)}
    if with_targets:
        b["targets"] = jax.random.randint(k2, (batch, seq), 0, cfg.vocab)
    if cfg.family == "audio":
        b["frames"] = jax.random.normal(k3, (batch, seq, cfg.d_model),
                                        jnp.float32).astype(cfg.dtype)
    if cfg.family == "vlm":
        b["image_embeds"] = jax.random.normal(
            k3, (batch, cfg.n_image_tokens, cfg.d_model),
            jnp.float32).astype(cfg.dtype)
    return b
