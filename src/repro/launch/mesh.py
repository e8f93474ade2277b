"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — the dry-run must
set XLA_FLAGS before anything calls into jax).

Mesh axes are `AxisType.Auto`: the model shell leaves shardings to the
partitioner (`with_sharding_constraint` hints, inferred gathers), which
explicit axes — `jax.make_mesh`'s default — refuse.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(n_devices: int | None = None, model: int = 2):
    """Tiny mesh for subprocess integration tests (8 host devices)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
