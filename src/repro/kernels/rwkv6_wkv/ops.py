"""Public WKV6 wrapper: [B,T,H,K] model layout in and out, head-major f32
kernel layout inside, interpret fallback off-accelerator."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.rwkv6_wkv.kernel import wkv6_bhtk


def wkv6(r, k, v, w, u, state, *, block_t: int = 64, interpret=None):
    """r/k/v/w: [B,T,H,K]; u: [H,K]; state: [B,H,K,K] f32.
    Returns (y [B,T,H,K] in r's dtype, final state f32)."""
    from repro.kernels import auto_interpret

    interp = auto_interpret() if interpret is None else interpret
    r_, k_, v_, w_ = (x.astype(jnp.float32).transpose(0, 2, 1, 3)
                      for x in (r, k, v, w))
    u_ = u.astype(jnp.float32)[:, None, :]
    y, s_out = wkv6_bhtk(r_, k_, v_, w_, u_, state.astype(jnp.float32),
                         block_t=block_t, interpret=interp)
    return y.transpose(0, 2, 1, 3).astype(r.dtype), s_out
