"""Launch/dry-run machinery tests at smoke scale (the 512-device runs live
in experiments/dryrun; here we prove the machinery on the in-process mesh).
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.configs import ARCH_IDS, SHAPES, get_config, supported_shapes
from repro.launch import hlo_analysis as H
from repro.launch.dryrun_lib import (
    TRAIN_MICROBATCHES,
    analytic_min_bytes,
    build_case,
    model_flops,
    rules_for,
)
from repro.launch.mesh import make_smoke_mesh
from repro.sharding.partition import partition_spec
from jax.sharding import AxisType, PartitionSpec as P


# --------------------------------------------------------------- hlo analysis
def test_hlo_analysis_counts_scan_trip_counts():
    def scanned(w, x):
        def body(c, _):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, None, length=10)
        return c.sum()

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 128), jnp.float32)
    txt = jax.jit(scanned).lower(w, x).compile().as_text()
    r = H.analyze(txt)
    assert r["flops"] == pytest.approx(2 * 8 * 128 * 128 * 10, rel=0.01)


def test_hlo_analysis_nested_scans_multiply():
    def nested(w, x):
        def outer(c, _):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        c, _ = jax.lax.scan(outer, x, None, length=4)
        return c.sum()

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    txt = jax.jit(nested).lower(w, x).compile().as_text()
    r = H.analyze(txt)
    assert r["flops"] == pytest.approx(2 * 4 * 64 * 64 * 20, rel=0.01)


def test_hlo_analysis_reports_collectives_under_sharding():
    mesh = make_smoke_mesh()  # 1x1 on CPU: no collectives expected
    txt = jax.jit(lambda a, b: (a @ b).sum()).lower(
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
    ).compile().as_text()
    r = H.analyze(txt)
    assert r["collective_bytes"] == 0.0
    assert r["flops"] > 0


# ----------------------------------------------------------- partition rules
def test_partition_divisibility_guard():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    # kv_heads=2 can't shard over model-size... on 1-device mesh everything
    # passes; test the pure function against a fake larger mesh via axis sizes
    spec = partition_spec((8, 14, 64), ("layers", "heads", None), mesh)
    assert isinstance(spec, P)


def test_rules_for_shapes():
    cfg = get_config("qwen3-1.7b")
    assert rules_for(cfg, SHAPES["train_4k"])["seq_res"] == "model"
    assert rules_for(cfg, SHAPES["long_500k"])["cache_seq"] == "data"
    assert rules_for(cfg, SHAPES["decode_32k"])["cache_seq"] == "model"


def test_model_flops_train_vs_inference():
    cfg = get_config("qwen3-1.7b")
    assert model_flops(cfg, SHAPES["train_4k"]) == pytest.approx(
        6 * 1.72e9 * 4096 * 256, rel=0.05)
    assert model_flops(cfg, SHAPES["decode_32k"]) == pytest.approx(
        2 * 1.72e9 * 128, rel=0.05)


def test_moe_model_flops_uses_active_params():
    dense = model_flops(get_config("qwen3-1.7b"), SHAPES["train_4k"])
    moe = model_flops(get_config("deepseek-moe-16b"), SHAPES["train_4k"])
    # 16B-total MoE has only 2.8B active
    assert moe < 2.5 * dense


def test_analytic_min_bytes_positive_and_ordered():
    cfg = get_config("gemma3-27b")
    tr = analytic_min_bytes(cfg, SHAPES["train_4k"], 256)
    de = analytic_min_bytes(cfg, SHAPES["decode_32k"], 256)
    assert tr > 0 and de > 0
    assert tr > de  # training touches params 4+ times + optimizer


# ----------------------------------------------- smoke-mesh build_case lower
@pytest.mark.parametrize("arch", ["qwen3-1.7b", "rwkv6-7b", "deepseek-moe-16b"])
def test_build_case_lowers_on_smoke_mesh(arch):
    """Reduced configs x all supported shapes lower+compile on the local mesh."""
    cfg = dataclasses.replace(
        get_config(arch).reduced(), dtype="float32")
    mesh = make_smoke_mesh()
    shape = dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=2)
    jf, sds = build_case(cfg, shape, mesh)
    compiled = jf.lower(*sds).compile()
    assert compiled.memory_analysis() is not None


def test_build_case_train_lowers_on_smoke_mesh():
    cfg = dataclasses.replace(get_config("qwen3-1.7b").reduced(), dtype="float32")
    mesh = make_smoke_mesh()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=2)
    jf, sds = build_case(cfg, shape, mesh)
    ca = jf.lower(*sds).compile().cost_analysis()
    assert ca.get("flops", 0) > 0


# -------------------------------------------------------- results sanity
DRYRUN = pathlib.Path(__file__).resolve().parent.parent / "experiments" / "dryrun"


# XLA:CPU does not alias the donated KV cache through the while carry (an
# extra cache-sized temp copy).  The one case this pushes past 16 GB; the
# structural requirement (analytic_min_bytes) fits comfortably and the same
# case fits on the multi-pod mesh.  See DESIGN.md §2 CPU-backend caveats.
KNOWN_CPU_ARTIFACT_OOM = {("deepseek-67b", "decode_32k", "16x16")}


@pytest.mark.skipif(not DRYRUN.exists() or not list(DRYRUN.glob("*.json")),
                    reason="dry-run artifacts not generated yet")
def test_dryrun_artifacts_complete_and_fit():
    """Every supported (arch x shape) must exist for both meshes and fit HBM."""
    missing, oom = [], []
    for arch in ARCH_IDS:
        for shape in supported_shapes(get_config(arch)):
            for mesh in ("16x16", "2x16x16"):
                f = DRYRUN / f"{arch}__{shape}__{mesh}.json"
                if not f.exists():
                    missing.append(f.name)
                    continue
                d = json.loads(f.read_text())
                if not d["memory"]["fits_hbm"]:
                    if (arch, shape, mesh) in KNOWN_CPU_ARTIFACT_OOM:
                        # the structural need must still fit
                        assert d["analytic_min_bytes_per_chip"] < 16e9
                        continue
                    oom.append((f.name, round(d["memory"]["peak_bytes"] / 1e9, 1)))
    assert not missing, missing
    assert not oom, oom
