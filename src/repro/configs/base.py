"""Base configuration system for the OnePiece reproduction.

Every assigned architecture is expressed as a :class:`ModelConfig`; the four
assigned input shapes are :class:`ShapeConfig`.  Configs are frozen
dataclasses so they can be hashed into jit static args.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    One instance per assigned architecture lives in ``repro/configs/<id>.py``.
    ``family`` selects the model implementation:
      dense | moe | ssm (rwkv6) | hybrid (zamba2, granite-4.0-h) | vlm |
      audio (enc-dec)
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    d_ff: int
    vocab_size: int
    num_kv_heads: int = 0            # 0 -> = num_heads (MHA)
    head_dim: int = 0                # 0 -> d_model // num_heads

    # --- attention features -------------------------------------------------
    qk_norm: bool = False
    rope: bool = True                # False: no positional encoding (NoPE)
    rope_theta: float = 10_000.0
    attention_multiplier: float = 0.0  # score scale; 0 -> 1/sqrt(head_dim)
    rope_2d: bool = False            # chatglm-style 2d rope (half-dim rotary)
    sliding_window: int = 0          # >0: window size for "local" layers
    local_global_pattern: Tuple[int, int] = (0, 0)  # (n_local, n_global) period
    attention_free: bool = False     # rwkv: no attention at all

    # --- MoE ----------------------------------------------------------------
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    first_dense_layers: int = 0      # leading dense layers (deepseek-moe)
    dense_ff: int = 0                # d_ff of those dense layers (0 -> d_ff)
    capacity_factor: float = 1.0
    router_experts: int = 0          # experts the router spans; 0 -> num_experts
    expert_offset: int = 0           # first of the num_experts held here
    shared_ff: int = 0               # shared-MLP width (0 -> num_shared_experts * d_ff)

    # --- SSM / hybrid -------------------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    hybrid_attn_every: int = 0       # shared attn block every N ssm layers
    attn_layer_period: int = 0       # >0: layer pattern of this period, all
    attn_layer_offset: int = 0       # Mamba2 but attention at this offset

    # --- encoder-decoder / frontend stubs ------------------------------------
    encoder_layers: int = 0
    cross_attention: bool = False
    frontend_tokens: int = 0         # stub embeddings (audio frames / patches)

    # --- misc ----------------------------------------------------------------
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    cache_dtype: str = ""            # "" -> same as dtype (serving knob)
    decode_unroll: int = 1           # lax.scan unroll for the decode layer loop
    attn_causal_skip: bool = False   # skip masked kv prefix blocks (§Perf)
    use_pallas: str = "auto"         # kernel dispatch: "auto" | "on" | "off"
                                     # (auto = Pallas on tpu/gpu; see docs/kernels.md)
    fsdp_weight_gather: bool = False # ZeRO-3: all-gather weights before dots
                                     # instead of all-reducing activations (§Perf)
    vocab_round: int = 256
    tie_embeddings: bool = False
    embedding_multiplier: float = 1.0  # muP scales (granite)
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0      # logits are divided by this
    source: str = ""                 # citation from the assignment pool

    # ------------------------------------------------------------------ utils
    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def resolved_kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def vocab_padded(self) -> int:
        return _round_up(self.vocab_size, self.vocab_round)

    @property
    def resolved_cache_dtype(self) -> str:
        return self.cache_dtype or self.dtype

    @property
    def resolved_router_experts(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def resolved_shared_ff(self) -> int:
        return self.shared_ff or self.num_shared_experts * self.d_ff

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    # --- parameter counting (for 6ND roofline sanity) ------------------------
    def param_count(self) -> int:
        """Total parameters (approximate; matches abstract_params to ~1%)."""
        from repro.models import registry  # local import to avoid cycle
        return registry.count_params(self)

    def active_param_count(self) -> int:
        from repro.models import registry
        return registry.count_active_params(self)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 layers, d_model<=512, <=4 experts.

        Keeps the *family* and every structural feature (GQA ratio, qk_norm,
        sliding pattern, shared experts, hybrid period) so smoke tests
        exercise the same code paths as the full config.
        """
        d_model = min(self.d_model, 256)
        head_dim = 32
        if self.family == "ssm":  # rwkv: heads * head_dim must equal d_model
            num_heads = d_model // head_dim
            num_kv = num_heads
        else:
            num_heads = max(2, d_model // 64)
            # preserve GQA grouping ratio approximately
            ratio = max(1, self.num_heads // max(1, self.resolved_kv_heads))
            num_kv = max(1, num_heads // ratio)
        num_experts = min(self.num_experts, 4) if self.num_experts else 0
        # an expert share keeps routing over more experts than it holds
        router = min(self.router_experts, 2 * num_experts) \
            if self.router_experts > self.num_experts else 0
        period = 2 if self.attn_layer_period else 0
        return replace(
            self,
            num_layers=2,
            d_model=d_model,
            num_heads=num_heads,
            num_kv_heads=num_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            dense_ff=min(self.dense_ff, 512) if self.dense_ff else 0,
            vocab_size=min(self.vocab_size, 1024),
            num_experts=num_experts,
            router_experts=router,
            shared_ff=min(self.shared_ff, 512) if self.shared_ff else 0,
            attn_layer_period=period,
            attn_layer_offset=min(self.attn_layer_offset, 1) if period else 0,
            num_shared_experts=min(self.num_shared_experts, 1),
            top_k=min(self.top_k, 2) if self.top_k else 0,
            first_dense_layers=min(self.first_dense_layers, 1),
            encoder_layers=2 if self.encoder_layers else 0,
            frontend_tokens=min(self.frontend_tokens, 16),
            hybrid_attn_every=2 if self.hybrid_attn_every else 0,
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            vocab_round=64,
        )


@dataclass(frozen=True)
class ShapeConfig:
    """One assigned input shape."""

    name: str
    seq_len: int
    global_batch: int
    mode: str  # "train" | "prefill" | "decode"

    @property
    def tokens(self) -> int:
        if self.mode == "decode":
            return self.global_batch  # one new token per sequence
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeConfig("train_4k", seq_len=4_096, global_batch=256, mode="train"),
    "prefill_32k": ShapeConfig("prefill_32k", seq_len=32_768, global_batch=32, mode="prefill"),
    "decode_32k": ShapeConfig("decode_32k", seq_len=32_768, global_batch=128, mode="decode"),
    "long_500k": ShapeConfig("long_500k", seq_len=524_288, global_batch=1, mode="decode"),
}

# Shapes each family/arch supports (see DESIGN.md §4 for the skip rationale).
LONG_CONTEXT_ARCHS = {"rwkv6-7b", "zamba2-1.2b", "gemma3-27b"}


def supported_shapes(cfg: ModelConfig):
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.name in LONG_CONTEXT_ARCHS:
        names.append("long_500k")
    return names


# --- TPU v5e hardware model for the roofline --------------------------------
@dataclass(frozen=True)
class HardwareConfig:
    name: str = "tpu_v5e"
    peak_flops_bf16: float = 197e12        # FLOP/s per chip
    hbm_bandwidth: float = 819e9           # B/s per chip
    ici_link_bandwidth: float = 50e9       # B/s per link (~ per chip per dir)
    hbm_bytes: float = 16e9                # capacity per chip
    vmem_bytes: float = 128 * 1024 * 1024  # ~128 MiB VMEM


V5E = HardwareConfig()
