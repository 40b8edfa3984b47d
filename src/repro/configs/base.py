"""Architecture configuration system.

Every assigned architecture is expressed as an ``ArchConfig``. A config is pure
data: the model builders in ``repro.models`` consume it, the profiler generates
variants from it, and the dry-run lowers it. ``reduced()`` produces a tiny
same-family config for CPU smoke tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

# Families understood by the model builder.
FAMILIES = ("dense", "moe", "hybrid", "ssm", "audio", "vlm")


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """Static architecture description (one per assigned architecture)."""

    name: str
    family: str                      # one of FAMILIES
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                        # for MoE: per-expert hidden size
    vocab: int

    # --- attention details ---
    head_dim: Optional[int] = None   # default: d_model // n_heads
    rope_theta: float = 500_000.0
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    capacity_factor: float = 1.25
    # --- SSM / hybrid ---
    ssm_state: int = 0               # Mamba2 state size N
    ssm_chunk: int = 256             # SSD chunk length
    shared_attn_every: int = 6       # zamba2: shared attention block period
    # --- xLSTM ---
    slstm_every: int = 4             # one sLSTM block per this many layers
    # --- audio (enc-dec) ---
    n_encoder_layers: int = 0
    # --- vlm ---
    cross_attn_every: int = 0        # 0 = no cross attention
    n_image_tokens: int = 0          # stub patch-embedding count
    # --- numerics / implementation ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # prefill's (and the paged pool's) attention path: "xla" | "pallas" |
    # "pallas_interpret". A serving engine's decode over the contiguous
    # cache takes the length-aware Pallas kernel on a TPU whatever this
    # says (in interpret mode under "pallas_interpret"); see
    # models.layers.attention_core
    attention_impl: str = "xla"
    # weight-only quantization of the projection matmuls (dense family):
    # "none" | "int8" (XLA dequant) | "int8_pallas" | "int8_pallas_interpret"
    # (the Pallas dequant-GEMM kernel / its interpret-mode build)
    quantize: str = "none"
    remat: bool = True
    # sub-quadratic sequence mixing? (gates long_500k applicability)
    subquadratic: bool = False
    # citation / provenance string
    source: str = ""

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown family {self.family!r}")
        if self.quantize not in ("none", "int8", "int8_pallas",
                                 "int8_pallas_interpret"):
            raise ValueError(f"unknown quantize mode {self.quantize!r}")
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.n_heads % max(self.n_kv_heads, 1) != 0:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    # ------------------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        """Analytic parameter count N (total, incl. all experts)."""
        return _param_count(self, active_only=False)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts only)."""
        return _param_count(self, active_only=True)

    # ------------------------------------------------------------------
    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, 4 if self.family != "hybrid" else 8),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=16,
            d_ff=128,
            vocab=256,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            n_shared_experts=min(self.n_shared_experts, 1),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_chunk=32,
            n_encoder_layers=min(self.n_encoder_layers, 2),
            cross_attn_every=min(self.cross_attn_every, 2) if self.cross_attn_every else 0,
            n_image_tokens=min(self.n_image_tokens, 16) if self.n_image_tokens else 0,
            dtype="float32",
            param_dtype="float32",
            remat=False,
        )


def _param_count(cfg: ArchConfig, active_only: bool) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    emb = cfg.vocab * d
    per_layer = 0
    # attention block (for families that have it on every layer)
    attn = d * cfg.n_heads * hd + 2 * d * cfg.n_kv_heads * hd + cfg.n_heads * hd * d
    ffn_dense = 3 * d * cfg.d_ff  # SwiGLU: gate, up, down
    if cfg.family in ("dense", "vlm"):
        per_layer = attn + ffn_dense
        if cfg.family == "vlm" and cfg.cross_attn_every:
            n_cross = cfg.n_layers // cfg.cross_attn_every
            per_layer_total = cfg.n_layers * per_layer + n_cross * attn
            return emb * 2 + per_layer_total
    elif cfg.family == "moe":
        n_e = (cfg.top_k + cfg.n_shared_experts) if active_only else (
            cfg.n_experts + cfg.n_shared_experts)
        per_layer = attn + n_e * 3 * d * cfg.d_ff + d * cfg.n_experts  # + router
    elif cfg.family == "hybrid":
        # Mamba2 block params: in_proj (x, z, B, C, dt) + out_proj
        d_inner = 2 * d
        mamba = d * (2 * d_inner + 2 * cfg.ssm_state + cfg.n_heads) + d_inner * d
        shared = attn + ffn_dense  # one shared transformer block (counted once)
        return emb * 2 + cfg.n_layers * mamba + shared
    elif cfg.family == "ssm":
        # xLSTM: mLSTM block (qkv + gates + out) ~ 8 d^2 ; sLSTM ~ 4.3 d^2 + ffn
        m_blk = 8 * d * d
        s_blk = 5 * d * d
        n_s = cfg.n_layers // cfg.slstm_every
        return emb * 2 + (cfg.n_layers - n_s) * m_blk + n_s * s_blk
    elif cfg.family == "audio":
        enc = cfg.n_encoder_layers * (attn + ffn_dense)
        dec = cfg.n_layers * (2 * attn + ffn_dense)
        return emb * 2 + enc + dec
    return emb * 2 + cfg.n_layers * per_layer
