"""Weight-only int8 quantization for the projection matmuls.

A quantized variant stores each projection weight as a ``QuantizedWeight``
— an int8 tensor in the original shape plus per-output-channel float32
scales — and dequantizes inside the matmul. Activations, KV cache,
embeddings, norms, and the LM head stay in the working dtype, so the
accuracy cost is bounded by per-channel symmetric rounding of the weights
(the classic weight-only W8A16/W8A32 recipe).

Three execution paths, selected by the config's ``quantize`` mode:

``int8`` (XLA)
    ``einsum(x, w_q.astype(f32)) * scales`` — dequantize in the epilogue,
    matching the Pallas kernel's accumulate-then-scale order so both
    paths agree bitwise on the same blocking.
``int8_pallas`` / ``int8_pallas_interpret``
    Reshape to a 2-D ``(M, K) @ (K, N)`` problem, pad to the kernel's
    block multiples, and call ``kernels.int8_matmul`` (interpret mode
    runs the same kernel through the Pallas interpreter on CPU).

``QuantizedWeight`` is a NamedTuple — automatically a pytree — so
quantized params slot into the existing param dict and survive
``jax.lax.scan`` slicing over stacked per-layer weights unchanged.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.int8_matmul import int8_matmul


class QuantizedWeight(NamedTuple):
    """Int8 weight in its original shape + per-output-channel scales.

    ``scales`` has the shape of the *non-contracted* dims of ``w_q``
    (quantization groups one scale per output channel), so
    ``dequant = w_q.astype(f32) * broadcast(scales)``.
    """

    w_q: jax.Array    # int8, original weight shape
    scales: jax.Array  # float32, shape = w_q.shape minus contracted axes


@functools.partial(jax.jit, static_argnames=("axes",))
def quantize_weight(w: jax.Array, axes: Tuple[int, ...]) -> QuantizedWeight:
    """Symmetric per-output-channel int8 quantization.

    ``axes`` are the contraction axes of the matmul this weight feeds
    (the reduction dims); one scale is kept per remaining (output)
    channel. absmax/127 scaling with a 1.0 floor on all-zero channels.
    Jitted so the float32 intermediates fuse away: run eagerly, each one
    of a stacked full-width weight is a whole copy (3.2 GB for
    phi3-mini's ``w_gate``), more than a 16 GB chip has to spare beside
    the bf16 tree being quantized.
    """
    w = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    scales = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    w_q = jnp.clip(jnp.round(w / scales), -127, 127).astype(jnp.int8)
    return QuantizedWeight(w_q, jnp.squeeze(scales, axis=axes))


# Contraction axes per projection weight name, for stacked (leading
# n_layers axis) dense-layer params. wq: (n,d,k,g,h) contracts d;
# wo: (n,k,g,h,d) contracts k,g,h; MLP mats contract their input dim.
_STACKED_AXES = {
    "wq": (1,), "wk": (1,), "wv": (1,),
    "wo": (1, 2, 3),
    "w_gate": (1,), "w_up": (1,),
    "w_down": (1,),
}


def quantize_params_dense(params) -> dict:
    """Quantize the projection weights of a dense-family param tree.

    Embedding, norms, and the LM head stay in the working dtype; the
    seven stacked projection weights under ``params["layers"]`` become
    ``QuantizedWeight``s. Returns a new tree (input is not mutated).
    """
    out = dict(params)
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    mlp = dict(layers["mlp"])
    for name in ("wq", "wk", "wv", "wo"):
        attn[name] = quantize_weight(attn[name], _STACKED_AXES[name])
    for name in ("w_gate", "w_up", "w_down"):
        mlp[name] = quantize_weight(mlp[name], _STACKED_AXES[name])
    layers["attn"] = attn
    layers["mlp"] = mlp
    out["layers"] = layers
    return out


def is_quantized(w) -> bool:
    return isinstance(w, QuantizedWeight)


def qimpl_for(quantize_mode: str) -> str:
    """Map a config ``quantize`` mode to a qeinsum impl name."""
    return {"int8": "xla", "int8_pallas": "pallas",
            "int8_pallas_interpret": "pallas_interpret"}[quantize_mode]


def _pad_to(n: int, block: int) -> int:
    block = min(block, n) if n >= 1 else block
    return ((n + block - 1) // block) * block


def qeinsum(spec: str, x: jax.Array, qw: QuantizedWeight,
            axes: Tuple[int, ...], impl: str = "xla") -> jax.Array:
    """Quantized einsum: ``einsum(spec, x, dequant(qw))``.

    ``axes`` are the contraction axes of ``qw`` (as passed to
    ``quantize_weight``). The XLA path evaluates the einsum directly
    with epilogue scaling; the pallas paths flatten to 2-D, pad to
    block multiples, and run the int8 GEMM kernel.
    """
    if impl == "xla":
        acc = jnp.einsum(spec, x.astype(jnp.float32),
                         qw.w_q.astype(jnp.float32))
        # scales lack the contracted axes, so they broadcast over the
        # output's trailing dims after aligning with expand_dims on the
        # *leading* batch dims of the output
        out_extra = acc.ndim - qw.scales.ndim
        return acc * jnp.expand_dims(qw.scales, tuple(range(out_extra)))

    # pallas / pallas_interpret: 2-D (M, K) @ (K, N) with padding
    interpret = impl == "pallas_interpret"
    w_q, scales = qw.w_q, qw.scales
    # move contracted axes of w to the front, flatten: (K, N)
    out_axes = tuple(i for i in range(w_q.ndim) if i not in axes)
    w2 = jnp.transpose(w_q, axes + out_axes)
    kd = 1
    for i in axes:
        kd *= w_q.shape[i]
    nd = 1
    for i in out_axes:
        nd *= w_q.shape[i]
    w2 = w2.reshape(kd, nd)
    s2 = scales.reshape(nd)
    # flatten x: the trailing len(axes) dims contract (they match w's
    # contracted axes in order for every projection spec); the rest batch
    batch_shape = x.shape[:x.ndim - len(axes)]
    x2 = x.astype(jnp.float32).reshape(-1, kd)
    m = x2.shape[0]
    mp, np_, kp = _pad_to(m, 128), _pad_to(nd, 128), _pad_to(kd, 256)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - kd)))
    w2 = jnp.pad(w2, ((0, kp - kd), (0, np_ - nd)))
    s2 = jnp.pad(s2, (0, np_ - nd))
    o = int8_matmul(x2, w2, s2, interpret=interpret)[:m, :nd]
    out_shape = tuple(batch_shape) + tuple(w_q.shape[i] for i in out_axes)
    return o.reshape(out_shape)
