"""Public SSD wrapper: [B,T,H,P] model layout in and out, head-major f32
kernel layout inside, the time axis padded to a whole number of chunks,
interpret fallback off-accelerator."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd_scan.kernel import ssd_scan_bhtp

CHUNK = 128


def ssd_scan(x, dt, la, B, C, state, *, chunk: int = CHUNK, interpret=None):
    """x: [B,T,H,P]; dt/la: [B,T,H] (step size, log decay); B/C: [B,T,N];
    state: [B,H,P,N].  Returns (y f32 [B,T,H,P], final state f32).

    Padded steps carry no input and no decay (dt x = 0, la = 0), so the
    state leaves them unchanged."""
    from repro.kernels import auto_interpret

    interp = auto_interpret() if interpret is None else interpret
    t = x.shape[1]
    chunk = chunk if t > chunk else -(-t // 8) * 8
    pad = (-t) % chunk
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).transpose(0, 2, 1, 3)
    la_ = la.astype(f32).transpose(0, 2, 1)              # [B,H,T]
    B_, C_ = B.astype(f32), C.astype(f32)
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        la_ = jnp.pad(la_, ((0, 0), (0, 0), (0, pad)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))
    y, s_out = ssd_scan_bhtp(xdt, la_[..., None], la_[:, :, None, :], B_, C_,
                             state.astype(f32), chunk=chunk,
                             interpret=interp)
    return y[:, :, :t].transpose(0, 2, 1, 3), s_out
