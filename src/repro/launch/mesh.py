"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism across pods (gradient all-reduce
crosses the inter-pod links only once per step).

Defined as functions so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh():
    """A trivial 1x1 mesh for CPU smoke tests of the sharded code paths."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """Auto axes: the sharded paths place arrays with
    `with_sharding_constraint`, which Explicit axes (the make_mesh
    default) reject."""
    return (jax.sharding.AxisType.Auto,) * n
