"""Uniform model API over all 10 assigned architecture families.

  abstract_params(cfg)                 -> ParamSpec tree
  init_params(key, cfg)                -> materialized params
  loss_fn(params, batch, cfg)          -> (loss, metrics)       [train_4k]
  prefill(params, batch, cfg)          -> (logits, cache)       [prefill_32k]
  decode_step(params, cache, batch, cfg)-> (logits, cache)      [decode shapes]
      (with dropless=True an MoE model adds its held-expert rows [B])
  abstract_cache(cfg, B, S)            -> ParamSpec tree
  input_specs(cfg, shape)              -> ParamSpec tree for the batch
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import numpy as np

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec, mamba2, rwkv6, transformer
from repro.models.param import ParamSpec, count, init_tree, is_spec

Tree = Dict[str, Any]

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": mamba2,
    "audio": encdec,
}


def module_for(cfg: ModelConfig):
    return _FAMILY[cfg.family]


def abstract_params(cfg: ModelConfig) -> Tree:
    return module_for(cfg).abstract_params(cfg)


def init_params(key, cfg: ModelConfig) -> Tree:
    return init_tree(key, abstract_params(cfg))


def loss_fn(params, batch, cfg: ModelConfig, **kw):
    return module_for(cfg).loss_fn(params, batch, cfg, **kw)


def prefill(params, batch, cfg: ModelConfig, **kw):
    return module_for(cfg).prefill(params, batch, cfg, **kw)


def decode_step(params, cache, batch, cfg: ModelConfig, **kw):
    return module_for(cfg).decode_step(params, cache, batch, cfg, **kw)


def abstract_cache(cfg: ModelConfig, batch: int, seq_len: int) -> Tree:
    return module_for(cfg).abstract_cache(cfg, batch, seq_len)


# ------------------------------------------------------------- input specs
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Tree:
    """ShapeDtypeStruct-able batch stand-ins (weak-type-correct, shardable).

    train:   tokens/labels [B,S] (+ stub frontend embeddings where needed)
    prefill: tokens [B,S] (+ stubs)
    decode:  tokens [B] + cur_index scalar (cache comes from abstract_cache)
    """
    b, s = shape.global_batch, shape.seq_len
    tok = ("batch", "seq")
    specs: Tree = {}
    if shape.mode == "decode":
        specs["tokens"] = ParamSpec((b,), ("batch",), "int32", "zeros")
        specs["cur_index"] = ParamSpec((), (), "int32", "zeros")
        return specs
    specs["tokens"] = ParamSpec((b, s), tok, "int32", "zeros")
    if shape.mode == "train":
        specs["labels"] = ParamSpec((b, s), tok, "int32", "zeros")
    if cfg.family == "vlm":
        p = min(cfg.frontend_tokens, s)
        specs["patch_embeds"] = ParamSpec(
            (b, p, cfg.d_model), ("batch", None, "act_embed"), cfg.dtype, "zeros"
        )
    if cfg.family == "audio":
        specs["frames"] = ParamSpec(
            (b, cfg.frontend_tokens, cfg.d_model), ("batch", None, "act_embed"),
            cfg.dtype, "zeros",
        )
    return specs


# --------------------------------------------------------------- counting
def count_params(cfg: ModelConfig) -> int:
    return count(abstract_params(cfg))


def count_active_params(cfg: ModelConfig) -> int:
    """Per-token active parameters (MoE: top_k of num_experts routed)."""
    tree = abstract_params(cfg)
    total = count(tree)
    if cfg.num_experts == 0:
        return total
    inactive_frac = 1.0 - cfg.top_k / cfg.resolved_router_experts
    expert = 0
    layers = tree["moe"] if "moe" in tree else tree["layers"]
    for name in ("we_gate", "we_up", "we_down"):
        expert += int(np.prod(layers[name].shape))
    return int(total - expert * inactive_frac)
