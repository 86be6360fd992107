"""granite-4.0-h-small [hybrid] — IBM Granite 4.0-H Small, 32B-A9B
[hf:ibm-granite/granite-4.0-h-small] (``model_type`` granitemoehybrid).

40 layers in periods of 10: nine Mamba2 mixers (128 heads of 64, d_state
128, one group, conv 4 with bias) and one GQA attention mixer (32/8 heads
of 128) at offset 5, with no positional encoding and scores scaled by
``attention_multiplier``.  After every mixer, a 72-expert MoE (top-10,
softmax over the top-10 router logits, expert width 768) plus a shared
SwiGLU MLP of width 1536.  muP scales: embeddings x12, each residual
branch x0.22, logits /16.  The config has no key of its own for the
expert width; ``intermediate_size`` (768) is read as that width.
"""
import dataclasses

from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    num_layers=40,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=768,               # per-expert FFN width (intermediate_size)
    vocab_size=100_352,
    rope=False,
    attention_multiplier=0.0078125,
    num_experts=72,
    router_experts=72,
    top_k=10,
    shared_ff=1536,
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    attn_layer_period=10,
    attn_layer_offset=5,
    norm_eps=1e-5,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
    source="hf:ibm-granite/granite-4.0-h-small",
)

# The share one chip holds of the 16-chip deployment (two pipeline stages of
# 20 layers, the 72 experts of each layer over 8 chips): the first stage,
# layers 0-19, and experts 0-8 of each layer, the router still over all 72.
# bench/configs/granite-4.0-h-small.json states the same cut.
ONE_CHIP = dataclasses.replace(CONFIG, num_layers=20, num_experts=9)
