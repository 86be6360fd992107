"""Building blocks of the plain references in ``bench/configs/*.py``.

Everything is straightforward ``jax.numpy`` in float32 with every matrix
product at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in
bfloat16 passes).  ``quant="fp8"`` gives the same arithmetic with both
operands of every product rounded to float8 e4m3 under a per-tensor scale:
the control, one precision below the configurations' bfloat16.  Nothing
here imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under a per-tensor absmax scale, back in f32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(eq: str, a: jax.Array, b: jax.Array, quant: str = "") -> jax.Array:
    a, b = a.astype(F32), b.astype(F32)
    if quant == "fp8":
        a, b = fp8(a), fp8(b)
    elif quant:
        raise ValueError(f"unknown quant {quant!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=F32)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(F32))


def layer_slice(stacked, i):
    """Layer ``i`` of a tree of ``[L, ...]`` leaves, in float32."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False).astype(F32),
        stacked)


def normal(key, shape, std, dtype, stacked=True):
    """Seeded normal weights made in ``dtype``; a stacked ``[L, ...]``
    leaf is made one layer at a time so that no float32 copy of the whole
    leaf is ever held."""
    def one(k, shp):
        return (jax.random.normal(k, shp, F32) * std).astype(dtype)
    if not stacked:
        return one(key, shape)
    return jax.lax.map(lambda k: one(k, shape[1:]),
                       jax.random.split(key, shape[0]))


def uniform(key, shape, lo, hi, dtype):
    return jax.random.uniform(key, shape, F32, lo, hi).astype(dtype)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
