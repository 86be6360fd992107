"""A checkout in miniature for the harness tests: a copy of ``bench/`` with
tiny configurations, a tiny mix and tiny cells added as new files and
entries, exactly as a later change would add them.  Runs on the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {
    "tiny-qwen3": {
        "registry": "qwen3-1.7b", "like": "qwen3-1.7b",
        "sizes": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 512, "vocab_round": 256,
                  "rope_theta": 1000000.0, "norm_eps": 1e-06,
                  "qk_norm": True, "tie_embeddings": True}},
    "tiny-rwkv6": {
        "registry": "rwkv6-7b", "like": "rwkv6-7b",
        "sizes": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                  "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                  "vocab_round": 256, "norm_eps": 1e-06,
                  "attention_free": True}},
}
MIX = {"arrivals": {"process": "poisson"},
       "prompt_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                         "round_up_to": [8, 16]},
       "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 4, "max": 16}}
CELL = {"rate_per_s": 8.0,
        "serving": {"max_len": 64, "slots": 4, "segment": 4,
                    "prefill_batch": 1},
        # set as a cell's limit is: above every sound reading of the tiny
        # models (at most 0.021 over seeds 1-3, CPU) and far below every
        # reading of their float8 control (at least 2.1)
        "check": {"limit_gap_rms": 0.1}}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """``tmp`` made into a checkout holding the repository's
    ``BENCHMARK.json`` and ``bench/`` plus one tiny cell per family."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, t in TINY.items():
        base = json.loads((BENCH / "configs" / f"{t['like']}.json").read_text())
        reg = __import__("repro.configs", fromlist=["get_config"]).get_config(
            t["registry"])
        changed = sorted(k for k, v in t["sizes"].items()
                         if getattr(reg, k) != v)
        cfg = dict(base, sizes=t["sizes"], reduced=changed)
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        shutil.copy(BENCH / "configs" / f"{t['like']}.py",
                    root / "bench" / "configs" / f"{name}.py")
        cell = f"{name}-tiny"
        (root / "bench" / "cells" / f"{cell}.json").write_text(
            json.dumps(CELL))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": changed, "why": "test"})
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny", "chips": 1,
                                   "why": "test"})
        for m in bench["per_layer"]:
            like = f"{t['like'].split('-')[0]}-chat"
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(MIX))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
