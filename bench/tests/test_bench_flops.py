"""bench/flops.py against counts worked out by hand for qwen3-1.7b
(28 layers, d 2048, 16 query and 8 KV heads of 128, d_ff 6144, vocab
151936 padded to 152064, tied embedding)."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import flops

S = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs" /
                "qwen3-1.7b.json").read_text())["sizes"]

# per layer: q 2048x16x128, k and v 2048x8x128, o 16x128x2048,
# gate/up/down 3 x 2048x6144
LAYER = 2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048 + 3 * 2048 * 6144
WEIGHTS = 2 * (28 * (LAYER + 2 * 2048 + 2 * 128) + 152064 * 2048 + 2048)
KV_PER_TOKEN = 2 * 28 * 8 * 128 * 2          # k and v, bf16


def test_layer_and_weight_counts():
    assert LAYER == 50_331_648
    assert flops.layer_matmul_params(S) == LAYER
    assert flops.weight_bytes(S) == WEIGHTS == 3_441_674_240
    assert KV_PER_TOKEN == 114_688        # 112 KiB


def test_decode_step_of_two_rows():
    # rows at 300 and 1500 cached positions attend 301 and 1501 keys
    got = flops.decode_step(S, [301, 1501])
    per_token = 2 * 28 * LAYER + 2 * 2048 * 151936
    attn = 4 * 28 * 16 * 128
    assert got["flops"] == 2 * per_token + attn * (301 + 1501)
    assert got["bytes"] == WEIGHTS + KV_PER_TOKEN * (301 + 1501)
    assert flops.decode_step(S, []) == {"flops": 0, "bytes": 0}


def test_prefill_of_one_prompt():
    n = 1024
    want = (n * 2 * 28 * LAYER + 2 * 2048 * 151936
            + 4 * 28 * 16 * 128 * n * (n + 1) // 2)
    assert flops.prefill_flops(S, 1, n) == want == 3_007_216_877_568
    assert flops.prefill_flops(S, 3, n) == 3 * want


def test_flash_kernel_and_roofline_bound():
    # FLOPs per byte ~ 16 S / 48: memory bound below S ~ 720 on a v5e
    small = flops.flash_attention(S, 4, 512)
    assert small["bytes"] == 28 * 4 * 512 * 128 * 2 * (2 * 16 + 2 * 8)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline(small, 1.0, peaks)["bound"] == "memory"
    w = flops.flash_attention(S, 4, 1024)
    assert w["flops"] == 28 * 4 * 2 * 16 * 128 * 1024 * 1025
    r = flops.roofline(w, w["flops"] / 197e12 * 2, peaks)
    assert r["bound"] == "compute" and r["share_pct"] == pytest.approx(50.0)
    d = flops.roofline(flops.decode_step(S, [2048] * 16), 1.0, peaks)
    assert d["bound"] == "memory"
