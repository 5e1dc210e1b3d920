"""Production mesh definitions.

A function, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(shape))


def mesh_chip_count(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
