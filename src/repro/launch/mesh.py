"""Production mesh construction.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (smoke tests see 1 CPU device; only dryrun.py forces 512).

Axes are Auto: the model code places arrays with sharding constraints and
lets the partitioner propagate, which Explicit axes (`jax.make_mesh`'s
default) reject at ops such as the vocab-sharded embedding gather.  Enter a
mesh with `jax.set_mesh(mesh)`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(dp: int, tp: int, pods: int = 1):
    """Arbitrary mesh for experiments / elastic remesh."""
    if pods > 1:
        return _auto_mesh((pods, dp, tp), ("pod", "data", "model"))
    return _auto_mesh((dp, tp), ("data", "model"))


def mesh_num_devices(mesh) -> int:
    return mesh.devices.size
