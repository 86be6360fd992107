"""A tiny granite-4.0-h-small cell for the harness tests, added to a
checkout in miniature (``tiny.make_root``) as new files and entries, as
the real cell is: two periods of one Mamba2 and one attention layer, 8 of
32 routed experts held here, each token routed to 8 (a quarter, as the
real cell holds an eighth and routes to 10 of 72), on the CPU.

At d_model 64 a router near-tie that bfloat16 rounding flips swaps an
expert that is an eighth of a token's MoE output, so sound runs read
more than the other tiny cells: at most 0.081 over seeds 11-15 (8 of 8
routed read 0.03-0.58 with top-2 of 8, 0.17 with 4 of 16), against at
least 3.29 for the float8 control.  The cell's limit, 0.5, lies 6x above
the one and 6.6x below the other."""
from __future__ import annotations

import json
import pathlib
import shutil

from bench.tests import tiny

NAME = "tiny-granite"
CELL = f"{NAME}-tiny"
KNOBS = dict(tiny.CELL, check={"limit_gap_rms": 0.5})
SIZES = {"num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 32, "vocab_size": 512, "vocab_round": 256,
         "norm_eps": 1e-05, "rope": False, "attention_multiplier": 0.0625,
         "num_experts": 8, "router_experts": 32, "expert_offset": 0,
         "top_k": 8, "shared_ff": 64, "ssm_state": 16, "ssm_head_dim": 16,
         "ssm_expand": 2, "attn_layer_period": 2, "attn_layer_offset": 1,
         "tie_embeddings": True, "embedding_multiplier": 12.0,
         "residual_multiplier": 0.22, "logits_scaling": 16.0}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """``tiny.make_root`` plus the tiny granite cell on the tiny mix."""
    root = tiny.make_root(tmp)
    base = json.loads((tiny.BENCH / "configs" /
                       "granite-4.0-h-small.json").read_text())
    from repro.configs import get_config

    reg = get_config(base["registry"])
    changed = sorted(k for k, v in SIZES.items() if getattr(reg, k) != v)
    cfg = dict(base, sizes=SIZES, reduced=changed)
    (root / "bench" / "configs" / f"{NAME}.json").write_text(json.dumps(cfg))
    shutil.copy(tiny.BENCH / "configs" / "granite-4.0-h-small.py",
                root / "bench" / "configs" / f"{NAME}.py")
    (root / "bench" / "cells" / f"{CELL}.json").write_text(
        json.dumps(KNOBS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": NAME, "source": "test",
                             "file": f"bench/configs/{NAME}.json",
                             "reduced": changed, "why": "test"})
    bench["workloads"].append({"name": CELL, "config": NAME,
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if "granite4h-chat" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
