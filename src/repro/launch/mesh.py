"""Production meshes.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state.  Single pod: 16x16 = 256 chips
("data", "model").  Multi-pod: 2x16x16 = 512 chips ("pod", "data",
"model") — the leading axis is the cross-pod (DCN) data-parallel axis.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh_compat(shape, axes):
    """``jax.make_mesh`` with every axis Auto-typed (the sharding mode all
    of the repo's ``shard_map`` programs are written for)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, model: int = 2):
    """Small mesh over however many (fake) devices are available."""
    n = n_devices or len(jax.devices())
    assert n % model == 0, (n, model)
    return make_mesh_compat((n // model, model), ("data", "model"))
