"""Operations and bytes that serving a dense model needs, from its shapes
and the live lengths the harness knows. Only what the algorithm needs is
counted: no padding to a bucket, no positions past a sequence's live
length, no slot that holds no request. A program that does less than this
count (skips dead positions, batches better) then reads closer to its
roofline honestly, and none can read above it.

Conventions: a multiply-add is 2 operations. Attention over ``c`` cached
positions costs 2 * 2 * H * D * c per layer per query token (scores and
the weighted sum). Decode reads every stored weight once per step and the
K and V of each live position of each live slot once per step.
"""
from __future__ import annotations

from typing import Iterable

from harness.weights import Dims


def proj_params(dm: Dims) -> int:
    """Projection weights of all layers (attention + MLP)."""
    d, H, K, D, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.ff
    return dm.layers * (d * H * D + 2 * d * K * D + H * D * d + 3 * d * f)


def weight_bytes(dm: Dims) -> int:
    """Bytes a decode step reads of the weights, as stored: projections
    (int8 plus a float32 scale per output channel, or bfloat16), the
    bfloat16 head and the norm weights. The embedding is a gather of one
    row per token and is counted with the tokens."""
    d, H, K, D, f = dm.d, dm.heads, dm.kv_heads, dm.head_dim, dm.ff
    p = proj_params(dm)
    if dm.projections == "int8":
        out_channels = dm.layers * (H * D + 2 * K * D + d + 2 * f + d)
        proj = p + 4 * out_channels
    else:
        proj = 2 * p
    norms = 2 * d * (2 * dm.layers + 1)
    return proj + 2 * dm.vocab * d + norms


def kv_bytes_per_token(dm: Dims) -> int:
    """bfloat16 K and V of one position in every layer."""
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * 2


def attn_flops(dm: Dims, pairs: int) -> int:
    """Attention over ``pairs`` (query, key) pairs in all: scores plus
    weighted sum, every query head, every layer."""
    return dm.layers * 4 * dm.heads * dm.head_dim * pairs


def prefill_flops(dm: Dims, length: int) -> int:
    """Prefill of one prompt of ``length`` real tokens: projections for
    every token, causal attention (L(L+1)/2 pairs), the head at the last
    position only (the program reads the next-token logits there)."""
    pairs = length * (length + 1) // 2
    return (2 * proj_params(dm) * length + attn_flops(dm, pairs)
            + 2 * dm.vocab * dm.d)


def decode_flops(dm: Dims, ctx: int) -> int:
    """One decode token whose query reads ``ctx`` cached positions (its
    own included): projections, attention, head."""
    return (2 * proj_params(dm) + attn_flops(dm, ctx)
            + 2 * dm.vocab * dm.d)


def request_decode(dm: Dims, prompt_len: int, token_idx: Iterable[int]):
    """(flops, kv_bytes) of the decode steps that produced the given
    output token indices of one request. Token 0 comes from prefill;
    token j >= 1 comes from the step at position L + j - 1, which reads
    L + j cached positions (its own written K and V included)."""
    flops = kv = 0
    for j in token_idx:
        if j < 1:
            continue
        ctx = prompt_len + j
        flops += decode_flops(dm, ctx)
        kv += ctx * kv_bytes_per_token(dm) + 2 * dm.d   # + embedding row
    return flops, kv
