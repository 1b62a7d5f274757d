"""Production mesh construction.

Axes:
  * ``pod``   — cross-pod data parallelism (only gradient/histogram psums
                cross this axis; DCI-friendly)
  * ``data``  — in-pod data parallelism (records / batch)
  * ``model`` — tensor / expert / field parallelism

Functions, not module-level constants: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
Every axis is ``Auto``: the trainers place data with explicit
``NamedSharding``s and collectives inside ``shard_map``, and leave the
rest to the partitioner (``jax.make_mesh`` would default to ``Explicit``
axes, which type-check every op's sharding).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType

SINGLE_POD_SHAPE: Tuple[int, int] = (16, 16)          # 256 chips / pod
MULTI_POD_SHAPE: Tuple[int, int, int] = (2, 16, 16)   # 2 pods = 512 chips


def _auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> jax.sharding.Mesh:
    """Arbitrary mesh over an explicit device list (elastic re-meshing)."""
    if devices is None:
        return _auto_mesh(shape, axes)
    devs = np.asarray(devices).reshape(tuple(shape))
    return jax.sharding.Mesh(devs, tuple(axes),
                             axis_types=(AxisType.Auto,) * len(axes))


def data_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Axes carrying record/batch parallelism (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def model_axis(mesh: jax.sharding.Mesh) -> str:
    return "model"


def n_data_shards(mesh: jax.sharding.Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
