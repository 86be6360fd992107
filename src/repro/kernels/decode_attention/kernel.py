"""Flash-decode Pallas TPU kernel: one query token against a long KV cache
(the decode_32k / long_500k hot-spot — strictly memory-bound, so the tiling
goal is streaming the cache through VMEM exactly once).

Grid: (batch, kv_heads, num_kv_blocks); trailing dim sequential with the
online-softmax state (m, l, acc over the q-group rows) in VMEM scratch.

The cache is the model's serving layout [B, KV, S, D] (GEMM-ready per
head): the kernels index it directly, so the dispatch layer never
relayouts the cache on the decode hot path.  Per-position int8 scales
arrive as [B, KV, 1, S], so their blocks end in (1, block_k) — the full
array dim and a lane multiple, as the TPU's (8, 128) tiling rule asks.

The int8 variants consume the quantized cache from ``quantize_kv`` without
materializing a dequantized block: k scales fold into the score matrix
([G, bk] multiplies) and v scales fold into the probabilities before the
value dot — O(G*bk) extra multiplies instead of O(bk*D) dequant work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_block(q, k, v, kj, block_k, cur, m_ref, l_ref, acc_ref,
                          k_scale=None, v_scale=None):
    """One kv block of the decode online softmax.  q: [G,D] (pre-scaled);
    k/v: [bk,D] f32; optional per-position scales [1, bk] fold into the
    score columns (k) and probabilities (v)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [G, bk]
    if k_scale is not None:
        s = s * k_scale
    pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos <= cur, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(jnp.maximum(m_prev, s.max(axis=-1)), -1e29)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    if v_scale is not None:
        p = p * v_scale
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new


def _finalize(o_ref, m_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, block_k: int, num_kv_blocks: int, sm_scale: float):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    cur = idx_ref[0]
    # skip cache blocks entirely beyond the valid prefix
    @pl.when(kj * block_k <= cur)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale        # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        _online_softmax_block(q, k, v, kj, block_k, cur, m_ref, l_ref, acc_ref)

    @pl.when(kj == num_kv_blocks - 1)
    def _fin():
        _finalize(o_ref, m_ref, l_ref, acc_ref)


def _decode_kernel_int8(idx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                        m_ref, l_ref, acc_ref, *, block_k: int,
                        num_kv_blocks: int, sm_scale: float):
    """int8-quantized cache variant: the cache feeds the dots directly and
    the per-(head, position) scales fold into scores / probabilities —
    HBM traffic is 1/2 of bf16, 1/4 of f32, with no dequantized block ever
    materialized in VMEM."""
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    cur = idx_ref[0]

    @pl.when(kj * block_k <= cur)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale                 # [G, D]
        kq = k_ref[0, 0].astype(jnp.float32)                           # [bk, D]
        vq = v_ref[0, 0].astype(jnp.float32)
        ks = ks_ref[0, 0]                                              # [1, bk]
        vs = vs_ref[0, 0]
        _online_softmax_block(q, kq, vq, kj, block_k, cur, m_ref, l_ref,
                              acc_ref, k_scale=ks, v_scale=vs)

    @pl.when(kj == num_kv_blocks - 1)
    def _fin():
        _finalize(o_ref, m_ref, l_ref, acc_ref)


def _state_scratch(g, d):
    return [
        pltpu.VMEM((g,), jnp.float32),
        pltpu.VMEM((g,), jnp.float32),
        pltpu.VMEM((g, d), jnp.float32),
    ]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_grouped_cache(q, k_cache, v_cache, cur_index, *,
                                   block_k=512, interpret=False):
    """q [B,KV,G,D]; k/v_cache [B,KV,S,D]; cur_index: int32 scalar."""
    b, kv, g, d = q.shape
    s = k_cache.shape[2]
    block_k = min(block_k, s)
    assert s % block_k == 0
    nk = s // block_k
    idx = jnp.asarray(cur_index, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               num_kv_blocks=nk, sm_scale=d ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b_, n, j: (b_, n, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, n, j: (b_, n, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, n, j: (b_, n, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, n, j: (b_, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=_state_scratch(g, d),
        interpret=interpret,
    )(idx, q, k_cache, v_cache)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_int8_grouped_cache(q, k_q, v_q, k_scale, v_scale,
                                        cur_index, *, block_k=512,
                                        interpret=False):
    """int8 variant: q [B,KV,G,D]; k_q/v_q int8 [B,KV,S,D]; scales f32
    [B,KV,1,S] — the model's int8 decode cache with a unit axis inserted
    (a free reshape), so the cache is handed over with zero relayout."""
    b, kv, g, d = q.shape
    s = k_q.shape[2]
    block_k = min(block_k, s)
    assert s % block_k == 0
    nk = s // block_k
    idx = jnp.asarray(cur_index, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel_int8, block_k=block_k,
                               num_kv_blocks=nk, sm_scale=d ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=(b, kv, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda b_, n, j: (b_, n, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, n, j: (b_, n, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, n, j: (b_, n, j, 0)),
            pl.BlockSpec((1, 1, 1, block_k), lambda b_, n, j: (b_, n, 0, j)),
            pl.BlockSpec((1, 1, 1, block_k), lambda b_, n, j: (b_, n, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, n, j: (b_, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
        scratch_shapes=_state_scratch(g, d),
        interpret=interpret,
    )(idx, q, k_q, v_q, k_scale, v_scale)
