"""Production mesh construction.

A function (not a module-level constant) so importing never touches JAX
device state; `dryrun.py` sets the 512-placeholder-device XLA flag
before its first jax import and then calls this.

Meshes use `AxisType.Auto` axes (`jax.make_mesh` defaults to Explicit):
the model code places activations with sharding constraints and leaves
the rest to the SPMD partitioner.  Activate one with `jax.set_mesh`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model single pod; (2,16,16) pod x data x model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def dp_axes(multi_pod: bool) -> tuple:
    return ("pod", "data") if multi_pod else ("data",)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires forced host device count)."""
    return _auto_mesh(shape, axes)
