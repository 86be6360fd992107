"""Mamba2 SSD recurrence Pallas TPU kernel (the prefill of the hybrid
models' Mamba2 layers).

Grid: (batch, heads, num_chunks) — trailing dim sequential, the state
S[P, N] of one head is VMEM scratch carried across chunks.  Within a chunk
of L steps the recurrence is evaluated in its matrix ("state space
duality") form, so every product runs on the MXU:

    cum_i   = sum_{j<=i} la_j                       (log decay to step i)
    y       = ((C B^T) * exp(cum_i - cum_j) [i>=j]) (dt x)
              + exp(cum) * (C S^T)                   (the carried state)
    S      <- exp(cum_L) S + ((dt x) * exp(cum_L - cum))^T B

BlockSpec tiling (per grid step, VMEM), head-major [B,H,T,*] layout:
  dt x: [1, 1, L, P]   la: [1, 1, L, 1] and [1, 1, 1, L]   B, C: [1, L, N]
  y:    [1, 1, L, P]   state io: [1, 1, P, N]
The log decay comes in twice, as a column and as a row, so that the
[L, L] decay matrix is a broadcast difference with no in-kernel transpose.
L = 128 (a lane-width row of la); P = 64, N = 128 for the published
Mamba2 widths: a [64, 128] f32 state tile is 32 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(xdt_ref, la_ref, lar_ref, b_ref, c_ref, s0_ref, y_ref,
                sout_ref, s_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = row >= col
    la = la_ref[0, 0]                                    # [L, 1]
    lar = lar_ref[0, 0]                                  # [1, L]
    cum = jnp.sum(jnp.where(lower, lar, 0.0), axis=1, keepdims=True)  # [L,1]
    cum_r = jnp.sum(jnp.where(row <= col, la, 0.0), axis=0,
                    keepdims=True)                       # [1, L]
    total = jnp.sum(la)
    decay = jnp.where(lower, jnp.exp(jnp.minimum(cum - cum_r, 0.0)), 0.0)

    xdt = xdt_ref[0, 0]                                  # [L, P]
    bm, cm = b_ref[0], c_ref[0]                          # [L, N]
    s = s_ref[...]                                       # [P, N]
    g = pl.dot(cm, bm, trans_b=True, precision=HIGHEST)  # [L, L]
    y = jnp.dot(g * decay, xdt, precision=HIGHEST,
                preferred_element_type=jnp.float32)
    y = y + jnp.exp(cum) * pl.dot(cm, s, trans_b=True, precision=HIGHEST)
    y_ref[0, 0] = y
    w = jnp.exp(total - cum)                             # [L, 1]
    s_ref[...] = jnp.exp(total) * s + pl.dot(xdt * w, bm, trans_a=True,
                                             precision=HIGHEST)

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        sout_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_bhtp(xdt, la_col, la_row, B, C, state, *, chunk=128,
                  interpret=False):
    """xdt: f32 [B,H,T,P] (dt * x); la_col: f32 [B,H,T,1] and la_row:
    f32 [B,H,1,T] (log decay); B/C: f32 [B,T,N]; state: f32 [B,H,P,N].
    T is a multiple of ``chunk``.  Returns (y f32 [B,H,T,P], final state)."""
    b, h, t, p = xdt.shape
    n = B.shape[-1]
    assert t % chunk == 0
    nc = t // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    seq = pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0))
    st = pl.BlockSpec((1, 1, p, n), lambda b_, h_, c: (b_, h_, 0, 0))
    bc = pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[seq,
                  pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c: (b_, h_, c, 0)),
                  pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c: (b_, h_, 0, c)),
                  bc, bc, st],
        out_specs=[seq, st],
        out_shape=[jax.ShapeDtypeStruct((b, h, t, p), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xdt, la_col, la_row, B, C, state)
