"""Pure-jnp oracle for the Mamba2 SSD recurrence (scan over time)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_ref(x, dt, la, B, C, state):
    """x: [B,T,H,P]; dt/la: [B,T,H] (step size, log decay); B/C: [B,T,N];
    state: [B,H,P,N].

        S_t = exp(la_t) S_{t-1} + (dt_t x_t) B_t^T ,   y_t = S_t C_t
    Returns (y [B,T,H,P], final state).
    """

    def step(s, xs):
        xt, dtt, lat, bt, ct = xs
        s = jnp.exp(lat)[..., None, None] * s + jnp.einsum(
            "bhp,bn->bhpn", xt * dtt[..., None], bt)
        return s, jnp.einsum("bhpn,bn->bhp", s, ct)

    xs = jax.tree.map(lambda a: a.swapaxes(0, 1), (x, dt, la, B, C))
    state, ys = jax.lax.scan(step, state, xs)
    return ys.swapaxes(0, 1), state
