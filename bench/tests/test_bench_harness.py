"""The harness end to end on the CPU at a tiny size: a whole run but the
look for a chip, with the served path sound and with it broken underneath;
new cells, configurations, mixes and metrics added as files and entries;
the exits without a chip and without the program."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness, spec
from bench.tests import tiny

CELLS = ["tiny-qwen3-tiny", "tiny-rwkv6-tiny"]
LIMIT = tiny.CELL["check"]["limit_gap_rms"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield tiny.make_root(tmp_path_factory.mktemp("bench"))
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def run(root, cell, seed=11, fault=None):
    return harness.run_cell(root, cell, seed=seed, seconds=1.0, trace=False,
                            t_process=time.perf_counter(), require_tpu=False,
                            fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell):
    out = run(root, cell)
    assert out["correct"], out["compared"]
    assert out["attempted"] == 8 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p50_ms", "tpot_mean_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "compared"
    assert out["compared"]["max_gap_rms"]["value"] <= LIMIT


# --- the timed path broken underneath: each must read as not correct ---
def altered_token(engine, decoder):
    """A token altered where it is produced."""
    seg, v = engine.decode_segment, engine.cfg.vocab_size

    def wrapped(state, k):
        state, toks, adv = seg(state, k)
        toks = toks.copy()
        toks[0] = (toks[0] + 1) % v
        return state, toks, adv
    engine.decode_segment = wrapped


def state_unchanged(engine, decoder):
    """A decode step that returns its state unchanged."""
    seg = engine.decode_segment

    def wrapped(state, k):
        _, toks, adv = seg(state, k)
        return state, toks, adv
    engine.decode_segment = wrapped


def half_handoff(engine, decoder):
    """Half of what the handoff carries left out: the second half of the
    heads (or state channels) of every shipped cache leaf arrives zeroed."""
    ins = engine.insert_slot

    def wrapped(state, slot, cache1, logits1, **kw):
        def cut(leaf):
            leaf = np.array(leaf)
            if leaf.ndim >= 3:
                leaf[:, :, leaf.shape[2] // 2:] = 0
            return leaf
        return ins(state, slot, jax.tree.map(cut, cache1), logits1, **kw)
    engine.insert_slot = wrapped


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [altered_token, state_unchanged,
                                   half_handoff])
def test_broken_path_is_not_correct(root, cell, fault):
    out = run(root, cell, fault=fault)
    assert not out["correct"], (fault.__doc__, out["compared"])
    assert out["compared"]["max_gap_rms"]["value"] > LIMIT


def test_a_new_metric_is_found_by_its_name(root, tmp_path):
    """A cell, configuration, mix (tiny.make_root) and a per-layer metric
    come in as new files and entries; nothing existing is edited."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "served_requests", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "ttft_p50_ms", "workloads": ["tiny-qwen3-tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "served_requests.py").write_text(
        "def reduce(ctx):\n    return sum(r.finished for r in ctx.records)\n")
    cell = spec.load_cell(root, "tiny-qwen3-tiny")
    names = [m["name"] for m in cell.metrics(trace=True)]
    assert "served_requests" in names and "flash_attention_roofline" in names
    assert "wkv6_roofline" not in names
    other = spec.load_cell(root, "tiny-rwkv6-tiny")
    assert "served_requests" not in [m["name"]
                                     for m in other.metrics(trace=True)]

    class Ctx:
        records = [type("R", (), {"finished": True})()] * 3
    assert cell.metric_reducer("served_requests").reduce(Ctx) == 3
    assert cell.config["sizes"]["num_layers"] == 2
    assert spec.program_config(cell.config).d_model == 64


def test_a_config_that_differs_from_the_registry_must_say_so(root):
    cell = spec.load_cell(root, "tiny-qwen3-tiny")
    cfg = dict(cell.config, reduced=[])
    with pytest.raises(spec.SpecError, match="not under 'reduced'"):
        spec.program_config(cfg)


def _bench(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result(root):
    p = _bench(tiny.REPO, "--workload", "qwen3-chat", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode == 3, p.stderr
    assert "no TPU" in p.stderr and "{" not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil

    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    p = _bench(tmp_path, "--workload", "qwen3-chat", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and "{" not in p.stdout
