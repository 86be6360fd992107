"""Config registry: ``get_config('<arch-id>')`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from repro.configs.base import (
    SHAPES,
    LONG_CONTEXT_ARCHS,
    HardwareConfig,
    ModelConfig,
    ShapeConfig,
    V5E,
    supported_shapes,
)

_ARCH_MODULES = {
    "deepseek-67b": "repro.configs.deepseek_67b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "internvl2-1b": "repro.configs.internvl2_1b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "qwen3-1.7b": "repro.configs.qwen3_1p7b",
    "gemma3-27b": "repro.configs.gemma3_27b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "granite-4.0-h-small": "repro.configs.granite_4h_small",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; available: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch_id]).CONFIG


def get_chip_config(arch_id: str) -> ModelConfig:
    """The share of ``arch_id`` one chip holds in its stated deployment
    (the module's ``ONE_CHIP``), or the whole config where one chip holds
    all of it."""
    get_config(arch_id)
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return getattr(mod, "ONE_CHIP", mod.CONFIG)


def get_shape(shape_id: str) -> ShapeConfig:
    if shape_id not in SHAPES:
        raise KeyError(f"unknown shape {shape_id!r}; available: {sorted(SHAPES)}")
    return SHAPES[shape_id]


def all_configs():
    return {aid: get_config(aid) for aid in ARCH_IDS}


__all__ = [
    "ARCH_IDS",
    "SHAPES",
    "LONG_CONTEXT_ARCHS",
    "HardwareConfig",
    "ModelConfig",
    "ShapeConfig",
    "V5E",
    "all_configs",
    "get_chip_config",
    "get_config",
    "get_shape",
    "supported_shapes",
]
