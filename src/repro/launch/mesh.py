"""Production mesh construction (function, not module constant — importing
this module never touches jax device state)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.models.partition import AxisInfo


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_axis_info(mesh: Mesh, *, shard_batch: bool = True) -> AxisInfo:
    names = mesh.axis_names
    data = tuple(n for n in names if n in ("pod", "data"))
    return AxisInfo(mesh=mesh, data=data, model="model",
                    shard_batch=shard_batch)


def make_host_mesh(shape: Tuple[int, ...] = (1, 1),
                   axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """Small mesh over however many (host) devices exist — used by tests."""
    return make_mesh(shape, axes)
