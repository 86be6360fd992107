"""Public flash-decode wrappers over the model's serving cache layout
[B,KV,S,D]: [B,H,D] query layout, GQA grouping, full-precision and
int8-quantized cache variants.  The dispatch layer in ``models/layers.py``
routes here so the decode hot path never transposes its cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import (
    decode_attention_grouped_cache,
    decode_attention_int8_grouped_cache,
)


def _auto(interpret):
    from repro.kernels import auto_interpret

    return auto_interpret() if interpret is None else interpret


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    s = x.shape[axis]
    if s == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - s)
    return jnp.pad(x, pads)


def decode_attention_cache(q, k_cache, v_cache, cur_index, *,
                           block_k: int = 512, interpret=None):
    """Serving-layout flash-decode: q [B,H,D]; k/v_cache [B,KV,S,D].
    Cache lengths that are not block multiples are zero-padded along S —
    the in-kernel ``pos <= cur_index`` mask already hides the padded tail.
    """
    b, h, d = q.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    qg = q.reshape(b, kv, h // kv, d)
    bk = max(8, min(block_k, _round_up(s, 8)))
    s_p = _round_up(s, bk)
    kc = _pad_axis(k_cache, 2, s_p)
    vc = _pad_axis(v_cache, 2, s_p)
    out = decode_attention_grouped_cache(qg, kc, vc, cur_index, block_k=bk,
                                         interpret=_auto(interpret))
    return out.reshape(b, h, d)


def quantize_kv(cache: jax.Array):
    """[B,S,KV,D] float -> (int8 values [B,S,KV,D], scales f32 [B,KV,S]).
    Per-(head, position) absmax scaling."""
    absmax = jnp.max(jnp.abs(cache.astype(jnp.float32)), axis=-1)  # [B,S,KV]
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(cache.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.transpose(0, 2, 1)  # scales in [B,KV,S]


def decode_attention_int8_cache(q, k_q, v_q, k_scale, v_scale, cur_index, *,
                                block_k: int = 512, interpret=None):
    """Serving-layout int8 flash-decode: q [B,H,D]; k_q/v_q int8 [B,KV,S,D];
    scales f32 [B,KV,S] — the exact arrays the model's int8 decode cache
    holds.  Scales fold into the score/value dots in-kernel; no dequantized
    cache block is ever materialized.  HBM traffic is 1/2 of a bf16 cache."""
    b, h, d = q.shape
    kv, s = k_q.shape[1], k_q.shape[2]
    qg = q.reshape(b, kv, h // kv, d)
    bk = max(8, min(block_k, _round_up(s, 8)))
    if bk < _round_up(s, 8):  # a partial scale block must be lane-aligned
        bk = _round_up(bk, 128)
    s_p = _round_up(s, bk)
    kq = _pad_axis(k_q, 2, s_p)
    vq = _pad_axis(v_q, 2, s_p)
    ks = _pad_axis(k_scale, 2, s_p)[:, :, None, :]
    vs = _pad_axis(v_scale, 2, s_p)[:, :, None, :]
    out = decode_attention_int8_grouped_cache(qg, kq, vq, ks, vs, cur_index,
                                              block_k=bk,
                                              interpret=_auto(interpret))
    return out.reshape(b, h, d)
