"""Public fused-DDIM-step wrapper: arbitrary latent shape, padded to
[rows, 128] lane-dense tiles, interpret fallback off-accelerator."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ddim_step.kernel import ddim_step_blocked


LANES = 128


def ddim_step(x: jax.Array, eps: jax.Array, alpha_t, alpha_prev, *,
              block_rows: int = 512, interpret=None) -> jax.Array:
    """Fused deterministic DDIM update: returns ``c1*x + c2*eps`` with the
    x0-prediction combine folded into the coefficients.  ``alpha_t`` /
    ``alpha_prev`` may be traced scalars (indexed out of the schedule inside
    the jitted sampling loop)."""
    from repro.kernels import auto_interpret

    a_t = jnp.asarray(alpha_t, jnp.float32)
    a_p = jnp.asarray(alpha_prev, jnp.float32)
    c1 = jnp.sqrt(a_p / a_t)
    c2 = jnp.sqrt(1.0 - a_p) - c1 * jnp.sqrt(1.0 - a_t)
    coefs = jnp.stack([c1, c2]).astype(jnp.float32)

    n = x.size
    rows = -(-n // LANES)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    rows_p = -(-rows // block_rows) * block_rows
    n_p = rows_p * LANES
    xf = jnp.pad(x.reshape(-1), (0, n_p - n)).reshape(rows_p, LANES)
    ef = jnp.pad(eps.reshape(-1).astype(x.dtype),
                 (0, n_p - n)).reshape(rows_p, LANES)

    interp = auto_interpret() if interpret is None else interpret
    out = ddim_step_blocked(xf, ef, coefs, block_rows=block_rows,
                            interpret=interp)
    return out.reshape(-1)[:n].reshape(x.shape)
