"""Fused DDIM-step Pallas TPU kernel for the Wan DiT sampling loop.

One deterministic (eta = 0) DDIM update is

    x0    = (x_t - sqrt(1 - a_t) * eps) / sqrt(a_t)
    x_t-1 = sqrt(a_p) * x0 + sqrt(1 - a_p) * eps

which algebraically collapses to a single fused-multiply-add per element:

    x_t-1 = c1 * x_t + c2 * eps
    c1    = sqrt(a_p / a_t)
    c2    = sqrt(1 - a_p) - c1 * sqrt(1 - a_t)

The reference path keeps the original two-step math (byte-compat with the
seed's DAG tests); the kernel does the combine + update in one pass over
the latent so each sampling step reads x/eps once and writes once instead
of materializing x0 and two broadcast intermediates.

The latent is flattened to lane-dense rows of 128 and tiled
[block_rows, 128] (block_rows a multiple of 8, the f32 sublane tile), so
every block satisfies the TPU (8, 128) tiling rule; coefficients ride in
SMEM so traced alphas (indexed out of the schedule inside the jitted
sampling loop) stay on-device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ddim_kernel(coef_ref, x_ref, eps_ref, o_ref):
    c1 = coef_ref[0]
    c2 = coef_ref[1]
    x = x_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    o_ref[...] = (c1 * x + c2 * eps).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ddim_step_blocked(x2d, eps2d, coefs, *, block_rows: int, interpret=False):
    """x2d/eps2d: [rows, 128] with rows % block_rows == 0; coefs: f32 [2]
    = (c1, c2)."""
    rows, lanes = x2d.shape
    assert rows % block_rows == 0, (rows, block_rows)
    return pl.pallas_call(
        _ddim_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x2d.dtype),
        interpret=interpret,
    )(coefs, x2d, eps2d)
