"""WKV6 recurrence Pallas TPU kernel (the rwkv6-7b hot loop).

Grid: (batch, heads, num_time_blocks) — trailing dim sequential, the
state matrix S[K,V] is VMEM scratch carried across time blocks; within a
block the recurrence runs as a fori_loop over one time row at a time.

BlockSpec tiling (per grid step, VMEM), head-major [B,H,T,K] layout so the
last two block dims are (block_t, K) — a multiple of 8 by the full head
size, which is what the TPU's (8, 128) tiling rule admits:
  r,k,v,w: [1, 1, block_t, K]   u: [1, 1, K]   (u as [H, 1, K])
  y:       [1, 1, block_t, K]   state io: [1, 1, K, K]
Inputs arrive in f32: the row loads index the sublane dim dynamically,
which Mosaic only proves aligned for 32-bit types.
K = head size = 64 for rwkv6-7b; a [64,64] f32 state tile is 16 KiB —
tiny against VMEM, so block_t mainly amortizes grid overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
                s_ref, *, block_t: int, num_t_blocks: int):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0]

    u = u_ref[0]                                  # [1, K]

    def step(i, _):
        row = (0, 0, pl.ds(i, 1), slice(None))
        rt, kt, vt, wt = r_ref[row], k_ref[row], v_ref[row], w_ref[row]  # [1, K]
        s = s_ref[...]                            # [K, V]
        y = jnp.dot(rt, s, preferred_element_type=jnp.float32) \
            + jnp.sum(u * kt * rt) * vt           # [1, V]
        y_ref[row] = y
        s_ref[...] = jnp.transpose(wt) * s + jnp.transpose(kt) * vt
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(tb == num_t_blocks - 1)
    def _finalize():
        sout_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6_bhtk(r, k, v, w, u, state, *, block_t=64, interpret=False):
    """r/k/v/w: f32 [B,H,T,K]; u: f32 [H,1,K]; state: f32 [B,H,K,K].
    Returns (y f32 [B,H,T,K], final state)."""
    b, h, t, kk = r.shape
    block_t = min(block_t, t)
    assert t % block_t == 0
    nt = t // block_t

    seq = pl.BlockSpec((1, 1, block_t, kk), lambda b_, h_, j: (b_, h_, j, 0))
    st = pl.BlockSpec((1, 1, kk, kk), lambda b_, h_, j: (b_, h_, 0, 0))
    kernel = functools.partial(_wkv_kernel, block_t=block_t, num_t_blocks=nt)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nt),
        in_specs=[seq, seq, seq, seq,
                  pl.BlockSpec((1, 1, kk), lambda b_, h_, j: (h_, 0, 0)),
                  st],
        out_specs=[seq, st],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, t, kk), jnp.float32),
            jax.ShapeDtypeStruct((b, h, kk, kk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((kk, kk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, state)
