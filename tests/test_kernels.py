"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel body in Python on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # JAX-heavy: excluded from the fast tier via -m "not slow"

from repro.kernels.ddim_step import ddim_step
from repro.kernels.ddim_step.ref import ddim_step_ref
from repro.kernels.decode_attention import (
    decode_attention_cache, decode_attention_int8_cache)
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6_wkv import wkv6
from repro.kernels.rwkv6_wkv.ref import wkv6_ref
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


TOLS = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
        jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kv,d,bq,bk", [
    (128, 2, 2, 64, 64, 64),
    (256, 4, 2, 64, 128, 128),
    (512, 2, 1, 128, 128, 256),
    (384, 3, 3, 32, 128, 128),   # uneven heads, non-square blocks
])
def test_flash_attention_sweep(dtype, s, h, kv, d, bq, bk):
    if s % bq or s % bk:
        pytest.skip("block mismatch")
    b = 2
    q = rand(0, (b, s, h, d), dtype)
    k = rand(1, (b, s, kv, d), dtype)
    v = rand(2, (b, s, kv, d), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    rep = h // kv
    ref = attention_ref(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


def test_flash_attention_non_causal():
    b, s, h, d = 1, 256, 2, 64
    q, k, v = (rand(i, (b, s, h, d), jnp.float32) for i in range(3))
    out = flash_attention(q, k, v, causal=False)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_matches_model_layer():
    """Kernel agrees with the model's blockwise attention path."""
    from repro.models.layers import attention_blockwise

    b, s, h, kvh, d = 2, 256, 4, 2, 64
    q = rand(3, (b, s, h, d), jnp.float32)
    k = rand(4, (b, s, kvh, d), jnp.float32)
    v = rand(5, (b, s, kvh, d), jnp.float32)
    ker = flash_attention(q, k, v, causal=True)
    mod = attention_blockwise(q, k, v, causal=True, q_block=64, kv_block=64)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(mod), atol=3e-5, rtol=3e-5)


# -------------------------------------------------------------------- ssd
def _ssd_inputs(b, t, h, p, n, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, h)) - 1.0)
    la = -jnp.exp(jax.random.normal(ks[2], (h,)))[None, None] * dt
    B = jax.random.normal(ks[3], (b, t, n))
    C = jax.random.normal(ks[4], (b, t, n))
    s0 = jax.random.normal(ks[5], (b, h, p, n))       # a carried state
    return x, dt, la, B, C, s0


@pytest.mark.parametrize("t,chunk", [(200, 64), (37, 128), (128, 128)])
def test_ssd_kernel_matches_the_scan(t, chunk):
    """The chunked SSD kernel (interpret mode) against the sequential scan,
    from a non-zero initial state, at lengths that are not a multiple of
    the chunk (padded steps must leave the state alone)."""
    args = _ssd_inputs(2, t, 3, 16, 32)
    with jax.default_matmul_precision("highest"):
        yr, sr = ssd_ref(*args)
    y, s = ssd_scan(*args, chunk=chunk, interpret=True)
    scale = float(jnp.abs(yr).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5 * scale, rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               atol=2e-5 * float(jnp.abs(sr).max()), rtol=0)


def test_ssd_kernel_matches_the_model_scan():
    from repro.models.mamba2 import ssd_scan as model_scan

    args = _ssd_inputs(1, 96, 2, 8, 16, key=3)
    with jax.default_matmul_precision("highest"):
        y_m, s_m = model_scan(*args, use_pallas=False)
    y_k, s_k = ssd_scan(*args, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_m),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------------- wkv6
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("t,h,kk,bt", [
    (64, 2, 32, 16),
    (128, 4, 64, 64),
    (96, 1, 64, 32),
])
def test_wkv6_sweep(dtype, t, h, kk, bt):
    b = 2
    r = rand(0, (b, t, h, kk), dtype)
    k = rand(1, (b, t, h, kk), dtype) * 0.3
    v = rand(2, (b, t, h, kk), dtype)
    w = jax.nn.sigmoid(rand(3, (b, t, h, kk), jnp.float32)) * 0.5 + 0.45
    w = w.astype(dtype)
    u = rand(4, (h, kk), dtype) * 0.1
    s0 = jnp.zeros((b, h, kk, kk), jnp.float32)
    y, s = wkv6(r, k, v, w, u, s0, block_t=bt)
    yr, sr = wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **TOLS[dtype])
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_wkv6_nonzero_initial_state_continuation():
    """Chunked calls with carried state == one full call (serving path)."""
    b, t, h, kk = 1, 64, 2, 32
    r, k, v = (rand(i, (b, t, h, kk), jnp.float32) for i in range(3))
    w = (jax.nn.sigmoid(rand(3, (b, t, h, kk), jnp.float32)) * 0.5 + 0.45)
    u = rand(4, (h, kk), jnp.float32) * 0.1
    s0 = jnp.zeros((b, h, kk, kk), jnp.float32)
    y_full, s_full = wkv6(r, k, v, w, u, s0, block_t=32)
    y1, s1 = wkv6(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0, block_t=32)
    y2, s2 = wkv6(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1, block_t=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4, rtol=1e-4)


def test_wkv6_matches_model_scan():
    from repro.models.rwkv6 import wkv6_scan

    b, t, h, kk = 2, 32, 2, 32
    r, k, v = (rand(i, (b, t, h, kk), jnp.float32) for i in range(3))
    w = (jax.nn.sigmoid(rand(9, (b, t, h, kk), jnp.float32)) * 0.5 + 0.45)
    u = rand(4, (h, kk), jnp.float32) * 0.1
    s0 = jnp.zeros((b, h, kk, kk), jnp.float32)
    y_k, s_k = wkv6(r, k, v, w, u, s0, block_t=16)
    y_m, s_m = wkv6_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_m), atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------ decode attention
def _serving(cache):
    """[B,S,KV,D] oracle layout -> the model's [B,KV,S,D] serving layout."""
    return cache.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,kv,d,bk,cur", [
    (512, 4, 2, 64, 128, 511),
    (512, 4, 2, 64, 128, 100),   # partially filled cache
    (1024, 8, 8, 128, 256, 700),
    (256, 2, 1, 32, 64, 0),      # single valid position
])
def test_decode_attention_sweep(dtype, s, h, kv, d, bk, cur):
    b = 2
    q = rand(0, (b, h, d), dtype)
    kc = rand(1, (b, s, kv, d), dtype)
    vc = rand(2, (b, s, kv, d), dtype)
    out = decode_attention_cache(q, _serving(kc), _serving(vc), jnp.int32(cur),
                                 block_k=bk)
    ref = decode_attention_ref(q, kc, vc, jnp.int32(cur))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOLS[dtype])


def test_decode_attention_matches_model_layer():
    from repro.models.layers import attention_decode

    b, s, h, kv, d = 2, 256, 4, 2, 64
    q = rand(0, (b, h, d), jnp.float32)
    kc = rand(1, (b, s, kv, d), jnp.float32)
    vc = rand(2, (b, s, kv, d), jnp.float32)
    out = decode_attention_cache(q, _serving(kc), _serving(vc), jnp.int32(77))
    mod = attention_decode(q, _serving(kc), _serving(vc), jnp.int32(77),
                           use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(mod), atol=3e-5, rtol=3e-5)


# ------------------------------------------------- int8 quantized cache
@pytest.mark.parametrize("s,h,kv,d,cur", [
    (512, 4, 2, 64, 511),
    (256, 8, 8, 128, 100),
])
def test_decode_attention_int8_matches_dequantized_oracle(s, h, kv, d, cur):
    from repro.kernels.decode_attention.ops import quantize_kv

    b = 2
    q = rand(0, (b, h, d), jnp.float32)
    kc = rand(1, (b, s, kv, d), jnp.float32)
    vc = rand(2, (b, s, kv, d), jnp.float32)
    k_q, k_s = quantize_kv(kc)
    v_q, v_s = quantize_kv(vc)
    out = decode_attention_int8_cache(q, _serving(k_q), _serving(v_q), k_s, v_s,
                                      jnp.int32(cur))
    # oracle on the dequantized cache: must match tightly
    deq_k = k_q.astype(jnp.float32) * k_s.transpose(0, 2, 1)[..., None]
    deq_v = v_q.astype(jnp.float32) * v_s.transpose(0, 2, 1)[..., None]
    ref = decode_attention_ref(q, deq_k, deq_v, jnp.int32(cur))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-4)
    # and the quantization error vs full precision stays small
    full = decode_attention_ref(q, kc, vc, jnp.int32(cur))
    err = float(jnp.abs(out - full).max())
    assert err < 0.05, err


def test_quantize_kv_roundtrip_error_bounded():
    from repro.kernels.decode_attention.ops import quantize_kv

    x = rand(3, (2, 64, 4, 32), jnp.float32) * 3.0
    q, s = quantize_kv(x)
    deq = q.astype(jnp.float32) * s.transpose(0, 2, 1)[..., None]
    rel = float(jnp.max(jnp.abs(deq - x)) / jnp.max(jnp.abs(x)))
    assert rel < 1.0 / 64  # absmax int8: error <= scale/2 ~ absmax/254


# ----------------------------------------- serving-layout cache kernels
@pytest.mark.parametrize("s,h,kv,d,cur", [
    (512, 4, 2, 64, 511),
    (384, 8, 8, 64, 100),
    (100, 2, 1, 32, 63),     # non-block-multiple cache length
])
def test_decode_attention_cache_layout(s, h, kv, d, cur):
    """[B,KV,S,hd] serving-layout kernel == [B,S,KV,hd] oracle (no relayout
    on the decode hot path)."""
    b = 2
    q = rand(0, (b, h, d), jnp.float32)
    kc = rand(1, (b, s, kv, d), jnp.float32)
    vc = rand(2, (b, s, kv, d), jnp.float32)
    out = decode_attention_cache(q, kc.transpose(0, 2, 1, 3),
                                 vc.transpose(0, 2, 1, 3), jnp.int32(cur))
    ref = decode_attention_ref(q, kc, vc, jnp.int32(cur))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("s,cur", [(512, 511), (100, 42)])
def test_decode_attention_int8_cache_layout(s, cur):
    """Fused int8-dequant kernel on the serving layout == oracle on the
    materialized dequantized cache (scales folded, never materialized)."""
    from repro.kernels.decode_attention.ops import quantize_kv

    b, h, kv, d = 2, 4, 2, 64
    q = rand(0, (b, h, d), jnp.float32)
    kc = rand(1, (b, s, kv, d), jnp.float32)
    vc = rand(2, (b, s, kv, d), jnp.float32)
    k_q, k_s = quantize_kv(kc)          # int8 [B,S,KV,hd], scales [B,KV,S]
    v_q, v_s = quantize_kv(vc)
    out = decode_attention_int8_cache(
        q, k_q.transpose(0, 2, 1, 3), v_q.transpose(0, 2, 1, 3),
        k_s, v_s, jnp.int32(cur))
    deq_k = k_q.astype(jnp.float32) * k_s.transpose(0, 2, 1)[..., None]
    deq_v = v_q.astype(jnp.float32) * v_s.transpose(0, 2, 1)[..., None]
    ref = decode_attention_ref(q, deq_k, deq_v, jnp.int32(cur))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------- non-block-multiple shapes
def test_flash_attention_non_block_multiple_causal():
    b, s, h, kv, d = 2, 80, 4, 2, 32    # 80 is not a multiple of any block
    q = rand(0, (b, s, h, d), jnp.float32)
    k = rand(1, (b, s, kv, d), jnp.float32)
    v = rand(2, (b, s, kv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, jnp.repeat(k, h // kv, 2), jnp.repeat(v, h // kv, 2),
                        causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_cross_shape_non_causal():
    # encoder-decoder cross attention: Sq != Sk, neither block-aligned
    b, sq, sk, h, d = 1, 80, 33, 2, 32
    q = rand(0, (b, sq, h, d), jnp.float32)
    k = rand(1, (b, sk, h, d), jnp.float32)
    v = rand(2, (b, sk, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=False)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_causal_cross_shape_raises():
    q = rand(0, (1, 64, 2, 32), jnp.float32)
    k = rand(1, (1, 32, 2, 32), jnp.float32)
    with pytest.raises(ValueError):
        flash_attention(q, k, k, causal=True)


# ------------------------------------------------------------ ddim step
@pytest.mark.parametrize("shape", [(4096,), (2, 1000, 16), (3, 7, 5)])
@pytest.mark.parametrize("a_t,a_p", [(0.7, 0.9), (0.02, 0.05), (0.98, 1.0)])
def test_ddim_step_matches_seed_math(shape, a_t, a_p):
    x = rand(0, shape, jnp.float32)
    eps = rand(1, shape, jnp.float32)
    out = ddim_step(x, eps, a_t, a_p)
    ref = ddim_step_ref(x, eps, a_t, a_p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert out.shape == shape


def test_ddim_step_traced_alphas():
    # alphas arrive as traced scalars inside the sampling scan
    x = rand(0, (512,), jnp.float32)
    eps = rand(1, (512,), jnp.float32)
    out = jax.jit(ddim_step)(x, eps, jnp.float32(0.6), jnp.float32(0.8))
    ref = ddim_step_ref(x, eps, 0.6, 0.8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        s=st.sampled_from([128, 256]),
        h=st.sampled_from([1, 2, 4]),
        d=st.sampled_from([32, 64]),
        cur=st.integers(0, 127),
    )
    def test_property_decode_attention_any_index(s, h, d, cur):
        b = 1
        q = rand(0, (b, h, d), jnp.float32)
        kc = rand(1, (b, s, h, d), jnp.float32)
        vc = rand(2, (b, s, h, d), jnp.float32)
        out = decode_attention_cache(q, _serving(kc), _serving(vc),
                                     jnp.int32(cur), block_k=64)
        ref = decode_attention_ref(q, kc, vc, jnp.int32(cur))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)
