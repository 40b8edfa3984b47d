"""Seeded random weights for a dense (llama-layout) configuration, made on
the device in one jitted call, in the types they are served in.

Every value comes from integer random bits times an f32 constant, then one
correctly rounded cast, so a layer made alone (``layer``) is bit for bit
the layer inside the whole tree (``make``): the plain reference makes its
own copy, layer by layer, from the same seed after the program's state is
freed, and takes nothing the program made.

Layout (leading layer axis on every per-layer leaf):
  embed (V, d), head (V, d), ln_f (d,), and per layer
  wq (d, K, G, D), wk (d, K, D), wv (d, K, D), wo (K, G, D, d),
  w_gate (d, f), w_up (d, f), w_down (f, d), ln1 (d,), ln2 (d,).
Norm weights ``s`` act as ``1 + s``. Projections are either bfloat16 or
int8 with one float32 scale per output channel: ``{"w_q", "scales"}``.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

# contraction axes of each per-layer projection (no layer axis)
CONTRACT = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1, 2),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,)}
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    rope_theta: float
    eps: float
    projections: str          # "int8" | "bfloat16"

    @property
    def groups(self) -> int:
        return self.heads // self.kv_heads

    def shapes(self) -> Dict[str, tuple]:
        d, K, G, D, f = (self.d, self.kv_heads, self.groups, self.head_dim,
                         self.ff)
        return {"wq": (d, K, G, D), "wk": (d, K, D), "wv": (d, K, D),
                "wo": (K, G, D, d), "w_gate": (d, f), "w_up": (d, f),
                "w_down": (f, d)}


def dims(cfg: Dict[str, Any]) -> Dims:
    return Dims(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], ff=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                projections=cfg["precision"]["projections"])


def root_key(seed: int):
    """A PRNG key from a seed of any size: low and high 32-bit words."""
    seed = int(seed) & ((1 << 64) - 1)
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def _uniform(key, shape, std: float, dtype):
    """Uniform on [-a, a] with the given std, from 16-bit integers."""
    k = jax.random.randint(key, shape, -32767, 32768, jnp.int32)
    c = jnp.float32(std * math.sqrt(3.0) / 32767.0)
    return (k.astype(jnp.float32) * c).astype(dtype)


def _proj(key, name: str, shape, dm: Dims):
    axes = CONTRACT[name]
    fan_in = math.prod(shape[a] for a in axes)
    std = 1.0 / math.sqrt(fan_in)
    if dm.projections == "bfloat16":
        return _uniform(key, shape, std, jnp.bfloat16)
    if dm.projections != "int8":
        raise ValueError(f"unknown projection precision {dm.projections!r}")
    kq, ks = jax.random.split(key)
    w_q = jax.random.randint(kq, shape, -127, 128, jnp.int32).astype(jnp.int8)
    out_shape = tuple(s for i, s in enumerate(shape) if i not in axes)
    # uniform int8 has std 127/sqrt(3); per-channel scales vary by +-25%
    base = jnp.float32(std * math.sqrt(3.0) / 127.0)
    jit = jax.random.randint(ks, out_shape, -16384, 16385, jnp.int32)
    scales = base * (1.0 + jit.astype(jnp.float32) * jnp.float32(0.25 / 16384))
    return {"w_q": w_q, "scales": scales}


def _layer(key, dm: Dims) -> Dict[str, Any]:
    shapes = dm.shapes()
    ks = jax.random.split(key, len(shapes) + 2)
    out = {n: _proj(k, n, shapes[n], dm) for k, n in zip(ks, shapes)}
    out["ln1"] = _uniform(ks[-2], (dm.d,), 0.1, jnp.bfloat16)
    out["ln2"] = _uniform(ks[-1], (dm.d,), 0.1, jnp.bfloat16)
    return out


def _keys(seed: int):
    r = root_key(seed)
    return {n: jax.random.fold_in(r, i) for i, n in
            enumerate(("layers", "embed", "head", "ln_f"))}


def _outer(dm: Dims, ks) -> Dict[str, Any]:
    return {"embed": _uniform(ks["embed"], (dm.vocab, dm.d), 1.0,
                              jnp.bfloat16),
            "head": _uniform(ks["head"], (dm.vocab, dm.d),
                             1.0 / math.sqrt(dm.d), jnp.bfloat16),
            "ln_f": _uniform(ks["ln_f"], (dm.d,), 0.1, jnp.bfloat16)}


@functools.lru_cache(maxsize=None)
def _jitted(dm: Dims):
    """The three generators of one configuration, jitted once each."""
    def build(ks):
        lk = jax.vmap(lambda i: jax.random.fold_in(ks["layers"], i))(
            jnp.arange(dm.layers))
        stacked = jax.lax.map(lambda k: _layer(k, dm), lk)
        return dict(_outer(dm, ks), layers=stacked)
    return (jax.jit(build), jax.jit(lambda k: _layer(k, dm)),
            jax.jit(lambda ks: _outer(dm, ks)))


def make(dm: Dims, seed: int) -> Dict[str, Any]:
    """The whole tree, stacked over layers, in one jitted call."""
    return _jitted(dm)[0](_keys(seed))


def layer(dm: Dims, seed: int, i: int) -> Dict[str, Any]:
    """Layer ``i`` alone: the same values as ``make(...)["layers"][i]``."""
    return _jitted(dm)[1](jax.random.fold_in(_keys(seed)["layers"], i))


def outer(dm: Dims, seed: int) -> Dict[str, Any]:
    """Embedding, head and final norm alone (same values as ``make``)."""
    return _jitted(dm)[2](_keys(seed))


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
