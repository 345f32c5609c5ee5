"""Production mesh construction.

Defined as FUNCTIONS (not module constants) so importing never touches
jax device state — the dry-run sets XLA_FLAGS before any jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the model's `constrain` hints are
    `with_sharding_constraint`s, which Explicit axes (jax.make_mesh's
    default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi_pod adds a 2-pod leading axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for smoke tests / examples."""
    return make_mesh((1, 1), ("data", "model"))
