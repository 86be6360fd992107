"""The granite-4.0-h-small configuration in the benchmark, on the CPU: its
file against the program's config, its weights maker against the
program's tree, its served path against the plain reference (a tiny cell
through the whole harness: sound, broken and the float8 control), the
SSD kernel's counts and its reducer on a recorded trace."""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
import types

import jax
import pytest

from bench import control, flops_hybrid, harness, spec, tracing
from bench.tests import test_bench_harness as faults
from bench.tests import tiny_granite

BENCH = pathlib.Path(__file__).resolve().parents[1]
FILE = BENCH / "configs" / "granite-4.0-h-small.json"
LIMIT = tiny_granite.KNOBS["check"]["limit_gap_rms"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield tiny_granite.make_root(tmp_path_factory.mktemp("bench"))
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def run(root, seed=11, fault=None):
    return harness.run_cell(root, tiny_granite.CELL, seed=seed, seconds=1.0,
                            trace=False, t_process=time.perf_counter(),
                            require_tpu=False, fault=fault)


# ------------------------------------------------------ the configuration
def test_the_file_is_the_one_chip_share_with_only_its_cuts_listed():
    from repro.configs.granite_4h_small import ONE_CHIP

    c = json.loads(FILE.read_text())
    cfg = spec.program_config(c)
    assert cfg == dataclasses.replace(ONE_CHIP, dtype="bfloat16")
    assert cfg.param_count() == 4_418_340_096
    # every width as published; the router over all 72, top-10
    s = c["sizes"]
    assert (s["d_model"], s["d_ff"], s["shared_ff"], s["vocab_size"]) == (
        c["hidden_size"], c["intermediate_size"],
        c["shared_intermediate_size"], c["vocab_size"]) == (
        4096, 768, 1536, 100352)
    assert (s["num_heads"], s["num_kv_heads"], s["head_dim"]) == (32, 8, 128)
    assert s["ssm_expand"] * s["d_model"] // s["ssm_head_dim"] == \
        c["mamba_n_heads"] == 128
    assert (s["ssm_head_dim"], s["ssm_state"]) == (64, 128)
    assert s["router_experts"] == c["published"]["num_experts"] == 72
    assert s["top_k"] == c["num_experts_per_tok"] == 10
    # the cuts: depth and the experts held, in both namings
    assert (s["num_layers"], c["num_hidden_layers"]) == (20, 20)
    assert (s["num_experts"], c["num_local_experts"]) == (9, 9)
    attn = [i for i, k in enumerate(c["layer_types"][:s["num_layers"]])
            if k == "attention"]
    assert attn == [s["attn_layer_offset"] + p * s["attn_layer_period"]
                    for p in range(2)]
    for cut in ("num_layers", "num_experts"):
        with pytest.raises(spec.SpecError, match="not under 'reduced'"):
            spec.program_config(dict(c, reduced=[k for k in c["reduced"]
                                                 if k != cut]))
    with pytest.raises(spec.SpecError, match="not under 'reduced'"):
        spec.program_config(dict(c, sizes=dict(s, d_ff=512)))


def test_the_weights_maker_gives_the_programs_tree(root):
    cell = spec.load_cell(root, tiny_granite.CELL)
    cfg = spec.program_config(cell.config)
    params = harness.make_params(cell.reference(), cell.config["sizes"], cfg,
                                 seed=5)
    assert set(params) == {"embedding", "final_norm", "mamba", "attn", "moe"}


# ----------------------------------------- served path against reference
def test_sound_run_is_correct(root):
    out = run(root)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert out["compared"]["max_gap_rms"]["value"] <= LIMIT


@pytest.mark.parametrize("fault", [faults.altered_token,
                                   faults.state_unchanged,
                                   faults.half_handoff])
def test_broken_path_is_not_correct(root, fault):
    out = run(root, fault=fault)
    assert not out["correct"], (fault.__doc__, out["compared"])
    assert out["compared"]["max_gap_rms"]["value"] > LIMIT


def test_the_float8_control_is_not_correct(root):
    """The reference one precision below bfloat16 reads far above the
    cell's limit where the program reads far below it."""
    r = control.readings(root, tiny_granite.CELL, 12, 1.0, require_tpu=False)
    assert r["program"] <= LIMIT < r["control"], r


# --------------------------------------------------------- the SSD kernel
def test_ssd_counts_worked_by_hand():
    s = tiny_granite.SIZES                 # 2 Mamba2 layers, 8 heads 16 x 16
    assert flops_hybrid.mamba_layers(s) == 2
    assert flops_hybrid.ssd_flops_per_token(s) == 8 * (5 * 16 * 16 + 16)
    # bytes per token (2*8*16 + 8 + 2*16) * 4 = 1184; state 8*16*16*4
    assert flops_hybrid.ssd(s, 1, 128) == {
        "flops": 2 * 128 * 10368, "bytes": 2 * (128 * 1184 + 2 * 8192)}
    full = json.loads(FILE.read_text())["sizes"]   # 18 Mamba2 layers
    assert flops_hybrid.mamba_layers(full) == 18
    assert flops_hybrid.ssd_flops_per_token(full) == 128 * (5 * 64 * 128 + 64)
    assert flops_hybrid.ssd(full, 1, 1)["bytes"] == 18 * (
        (2 * 128 * 64 + 128 + 256) * 4 + 2 * 128 * 64 * 128 * 4)


def _ctx(name):
    d = json.loads((BENCH / "tests" / "data" / name).read_text())
    tr = tracing.Trace.from_json(d["trace"])
    busy = tracing.union_ns((s, e) for _, s, e in tr.ops) / 1e9
    return tracing.Context(tr, {int(k): v for k, v in d["calls"].items()},
                           d["counters"],
                           [types.SimpleNamespace(**r) for r in d["records"]],
                           d["sizes"], d["knobs"], d["peaks"], tr.window_s,
                           busy)


def test_ssd_roofline_on_a_recorded_trace():
    reducer = spec.load_module(BENCH / "metrics" / "ssd_roofline.py", "m_ssd")
    # one prefill of 1 x 128: 335872 bytes -> 33.5872 ms at 1e7 B/s (FLOPs
    # 2654208 -> 2.654 ms at 1e9), over two kernel runs of 25 ms
    assert reducer.reduce(_ctx("trace_ssd.json")) == pytest.approx(
        67.1744, rel=1e-9)
    # no SSD kernel in the dense model's trace: nothing to report
    assert reducer.reduce(_ctx("trace_small.json")) is None
