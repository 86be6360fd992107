"""Production mesh builders.

Functions (not module-level constants) so importing this module never touches
jax device state — ``dryrun.py`` must set XLA_FLAGS before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """Mesh with Auto axes: the models place activations through
    ``with_sharding_constraint``, which Explicit axes (the make_mesh
    default) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256-chip v5e pod) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_smoke_mesh():
    """Single-device mesh with the production axis names (for CPU tests)."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
