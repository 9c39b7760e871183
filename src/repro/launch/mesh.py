"""Production mesh construction.

Single pod : (16, 16)      -> ("data", "model")          256 chips
Multi pod  : (2, 16, 16)   -> ("pod", "data", "model")   512 chips

A *function*, not a module constant: importing this module must never
touch JAX device state (the dry-run sets the fake-device XLA flag before
its first jax import, and smoke tests must keep seeing 1 CPU device).

Axis semantics mirror the paper's communicator hierarchy (DESIGN.md):
"data"+"model" are the fast intra-pod ICI tiers (the paper's *local*
communicator: threads + processes of one node), "pod" is the slow
inter-pod tier (the paper's *global* communicator across nodes).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_single_device_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_single_device_mesh():
    """1-device mesh with the production axis names (tests / laptops)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
