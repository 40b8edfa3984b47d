"""Plain float32 reference of Yi-9B (01.AI, arXiv:2403.04652), a decoder in
the llama layout with grouped-query attention:

    h   = x + Attn(RMSNorm(x)),  y = h + MLP(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + s)
    Attn: q (32 heads), k and v (4 heads) projections of 128, rotary
          embedding on q and k (rotate-half form), causal
          softmax(q k^T / sqrt(128)) v with query head h reading
          key/value head h // 8, output projection
    MLP(x) = (silu(x W_gate) * (x W_up)) W_down,  d_ff 11,008
    logits = RMSNorm_f(x_L) head^T       (untied head, vocab 64,000)

These are the equations of ``dense_ref.py``, whose layer math handles
grouped heads; this module loads that file and states Yi's departures.
Departures from the published model: norm weights act as ``1 + s`` (the
same function with the weight shifted by one), weights are random, and
the stage runs 24 of the 48 layers with the embedding and the head
beside them.

``CONTROLS`` put a precision below the configuration's bfloat16 in the
reference's place: weight-only int8 (per output channel), the next one
down, and weight-only int4. The configuration's ``control`` names the
one its cell is calibrated against (PERF.md).
"""
from __future__ import annotations

from pathlib import Path

from harness.spec import load_module

_dense = load_module(Path(__file__).with_name("dense_ref.py"),
                     "chipbench_ref_dense_ref")

dequant = _dense.dequant
layer = _dense.layer
logits = _dense.logits
embed = _dense.embed

CONTROLS = {
    # weight-only int8 in place of bfloat16 projections
    "int8": lambda w, contract: _dense.int_fake_quant(w, contract, 8),
    # weight-only int4
    "int4": lambda w, contract: _dense.int_fake_quant(w, contract, 4),
}
