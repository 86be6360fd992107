"""Dry-run machinery: lower + compile every (arch x shape x mesh) case with
ShapeDtypeStruct stand-ins (no allocation), extract memory / cost / collective
statistics, and derive the three roofline terms.

NOTE: this module must be imported AFTER the XLA_FLAGS device-count env var
is set (``repro.launch.dryrun`` does that in its first two lines).
"""
from __future__ import annotations

import dataclasses
import json
import re
import time
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import V5E, ModelConfig, ShapeConfig, get_config, get_shape
from repro.models import registry
from repro.models.param import ParamSpec, abstract_tree, is_spec, use_partitioner
from repro.sharding.partition import Partitioner
from repro.training.optimizer import adamw_abstract
from repro.training.train_step import make_train_step

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand sizes of every collective op in the (SPMD) module.

    The module is the per-device program, so these are per-chip wire bytes.
    """
    per_kind: Dict[str, int] = {}
    count: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.search(r"= *[a-z0-9\[\],{} ]*\b(" + "|".join(_COLLECTIVES) + r")\(", line)
        if not m:
            # also catch fusion-wrapped starts like all-gather-start
            m = re.search(r"\b(" + "|".join(_COLLECTIVES) + r")-start\(", line)
            if not m:
                continue
        kind = m.group(1)
        shapes = _SHAPE_RE.findall(line)
        if not shapes:
            continue
        # first shape on the line is the result; the rest are operands
        operands = shapes[1:] if len(shapes) > 1 else shapes
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in operands)
        per_kind[kind] = per_kind.get(kind, 0) + nbytes
        count[kind] = count.get(kind, 0) + 1
    return {"bytes_by_kind": per_kind, "count_by_kind": count,
            "total_bytes": sum(per_kind.values())}


# Per-arch microbatch counts for train_4k: chosen so activations + backward
# reshard buffers fit 16 GB HBM (a §Perf knob — see EXPERIMENTS.md).
TRAIN_MICROBATCHES = {
    "deepseek-67b": 8,
    "gemma3-27b": 8,
    "chatglm3-6b": 2,
    "internvl2-1b": 1,
    "granite-moe-3b-a800m": 2,
    "deepseek-moe-16b": 1,
    "rwkv6-7b": 2,
    "zamba2-1.2b": 2,
    "qwen3-1.7b": 1,
    "whisper-large-v3": 2,
}


# ---------------------------------------------------------------- rule sets
def rules_for(cfg: ModelConfig, shape: ShapeConfig,
              overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    rules: Dict[str, Any] = {}
    if shape.mode == "train":
        # Megatron-style sequence parallelism on the residual stream: needed
        # for the 4k x 256 activations of the big archs to fit 16 GB HBM.
        rules["seq_res"] = "model"
    if shape.mode in ("prefill", "decode"):
        if shape.name == "long_500k":
            rules["cache_seq"] = "data"   # context-parallel full-attn caches
        else:
            # shard the cache sequence dim over `model` — works even when
            # kv_heads < model axis (deepseek-67b kv=8, granite kv=8, ...)
            rules["cache_seq"] = "model"
            rules["cache_kv_heads"] = None
    rules.update(overrides or {})
    return rules


# -------------------------------------------------------------- case builder
def build_case(cfg: ModelConfig, shape: ShapeConfig, mesh,
               rule_overrides: Optional[Dict[str, Any]] = None):
    """Returns (jitted_fn, arg_sds, donate) ready for .lower(*arg_sds)."""
    if shape.mode in ("prefill", "decode") and not cfg.cache_dtype:
        # CPU dry-run uses f32 KV caches: XLA:CPU legalizes bf16 dots by
        # keeping full f32 mirrors of the (while-carried) cache, doubling
        # temp memory.  TPU has native bf16 MXU dots; a bf16 cache there is
        # strictly SMALLER than what we prove fits here.  (Documented in
        # DESIGN.md §2 hardware-adaptation notes.)
        cfg = dataclasses.replace(cfg, cache_dtype="float32")
    part = Partitioner(mesh, rules_for(cfg, shape, rule_overrides))
    pspecs = registry.abstract_params(cfg)
    p_sh = part.tree_shardings(pspecs)
    p_sds = abstract_tree(pspecs)
    batch_specs = registry.input_specs(cfg, shape)
    b_sh = part.tree_shardings(batch_specs)
    b_sds = abstract_tree(batch_specs)
    scalar = NamedSharding(mesh, P())

    if shape.mode == "train":
        opt_specs = adamw_abstract(pspecs)
        o_sh = part.tree_shardings(opt_specs)
        o_sds = abstract_tree(opt_specs)
        step = make_train_step(
            cfg, microbatches=TRAIN_MICROBATCHES.get(cfg.name, 1))

        def fn(params, opt, batch):
            with use_partitioner(part):
                p2, o2, m = step(params, opt, batch)
            return p2, o2, m["loss"]

        jf = jax.jit(fn, in_shardings=(p_sh, o_sh, b_sh),
                     out_shardings=(p_sh, o_sh, scalar),
                     donate_argnums=(0, 1))
        return jf, (p_sds, o_sds, b_sds)

    logits_spec = ParamSpec((shape.global_batch, cfg.vocab_padded),
                            ("batch", "act_vocab"), "float32")
    l_sh = part.sharding(logits_spec.shape, logits_spec.logical)

    if shape.mode == "prefill":
        cache_specs = registry.abstract_cache(cfg, shape.global_batch, shape.seq_len)
        c_sh = part.tree_shardings(cache_specs)

        def fn(params, batch):
            with use_partitioner(part):
                return registry.prefill(params, batch, cfg)

        jf = jax.jit(fn, in_shardings=(p_sh, b_sh), out_shardings=(l_sh, c_sh))
        return jf, (p_sds, b_sds)

    # decode
    cache_specs = registry.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    c_sh = part.tree_shardings(cache_specs)
    c_sds = abstract_tree(cache_specs)

    def fn(params, cache, batch):
        with use_partitioner(part):
            return registry.decode_step(params, cache, batch, cfg)

    jf = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                 out_shardings=(l_sh, c_sh), donate_argnums=(1,))
    return jf, (p_sds, c_sds, b_sds)


# ------------------------------------------------------------------ roofline
def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """6*N*D (train) / 2*N*D (inference), N = active params."""
    n = registry.count_active_params(cfg)
    mult = 6.0 if shape.mode == "train" else 2.0
    return mult * n * shape.tokens


def analytic_min_bytes(cfg: ModelConfig, shape: ShapeConfig, n_chips: int) -> float:
    """Structural lower bound on HBM traffic per chip per step: weights/
    optimizer/cache must be touched at least this much.  The HLO-derived
    ``bytes_per_chip`` is an upper-bound proxy; the truth lies between."""
    import numpy as _np

    pbytes = 2.0 * registry.count_params(cfg)  # bf16
    cache_specs = (registry.abstract_cache(cfg, shape.global_batch, shape.seq_len)
                   if shape.mode != "train" else {})
    cbytes = sum(
        _np.prod(s.shape) * (2 if s.dtype == "bfloat16" else 4)
        for s in jax.tree.leaves(cache_specs, is_leaf=is_spec)
    )
    act = 2.0 * shape.tokens * cfg.d_model  # one residual pass, bf16
    if shape.mode == "train":
        # fwd + bwd + remat reads of params, grads write, adamw rw (f32 m,v)
        total = pbytes * 3 + pbytes + 4.0 * registry.count_params(cfg) * 4 + act * 8
    elif shape.mode == "prefill":
        total = pbytes + cbytes + act * 4
    else:  # decode: read all params + read cache + write one slot
        total = pbytes + cbytes + act
    return float(total) / n_chips


def roofline_terms(stats: Dict[str, Any], hw=V5E) -> Dict[str, float]:
    """cost_analysis numbers are per-device; terms are per-chip seconds."""
    compute_s = stats["flops_per_chip"] / hw.peak_flops_bf16
    memory_s = stats["bytes_per_chip"] / hw.hbm_bandwidth
    collective_s = stats["collective_bytes_per_chip"] / hw.ici_link_bandwidth
    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dominant}


def run_case(arch: str, shape_id: str, *, multi_pod: bool = False,
             rule_overrides: Optional[Dict[str, Any]] = None,
             cfg_overrides: Optional[Dict[str, Any]] = None,
             microbatches: Optional[int] = None,
             hw=V5E) -> Dict[str, Any]:
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    if microbatches is not None:
        TRAIN_MICROBATCHES[cfg.name] = microbatches
    shape = get_shape(shape_id)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.devices.shape)))

    t0 = time.time()
    jf, sds = build_case(cfg, shape, mesh, rule_overrides)
    lowered = jf.lower(*sds)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    # trip-count-aware totals (XLA cost_analysis counts while bodies once —
    # useless for scan-over-layers models; see launch/hlo_analysis.py)
    ana = __import__("repro.launch.hlo_analysis", fromlist=["analyze"]).analyze(hlo_text)

    flops_pc = float(ana["flops"])
    bytes_pc = float(ana["bytes_hbm"])
    peak_bytes = int(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    )
    stats = {
        "arch": arch,
        "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_chip": flops_pc,
        "bytes_per_chip": bytes_pc,
        "collective_bytes_per_chip": float(ana["collective_bytes"]),
        "collectives": {
            "bytes_by_kind": ana["collective_bytes_by_kind"],
            "count_by_kind": ana["collective_count_by_kind"],
            "total_bytes": ana["collective_bytes"],
        },
        "xla_cost_analysis": {
            "flops_once": float(ca.get("flops", 0.0)),
            "bytes_accessed_once": float(ca.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_bytes": peak_bytes,
            "fits_hbm": bool(peak_bytes <= hw.hbm_bytes),
        },
        "tokens": shape.tokens,
        "model_flops": model_flops(cfg, shape),
        "hlo_flops_total": flops_pc * n_chips,
        "analytic_min_bytes_per_chip": analytic_min_bytes(cfg, shape, n_chips),
    }
    stats["useful_flops_ratio"] = (
        stats["model_flops"] / stats["hlo_flops_total"]
        if stats["hlo_flops_total"] else 0.0
    )
    stats.update(roofline_terms(stats, hw))
    return stats


def case_list():
    """All 40 baseline (arch x shape) pairs honoring the skip rules."""
    from repro.configs import ARCH_IDS, supported_shapes

    cases = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for s in supported_shapes(cfg):
            cases.append((arch, s))
    return cases
