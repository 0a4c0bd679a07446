"""Production meshes (functions — importing this module never touches jax
device state).

Single pod: 256 TPU v5e chips, mesh (16, 16) = ("data", "model").
Multi-pod: 2 pods = 512 chips, mesh (2, 16, 16) = ("pod", "data", "model")
— "pod" is the slow (DCN) axis; only DP gradient all-reduce (or pipeline
stages) crosses it. Per-chip peaks live in :mod:`repro.roofline.peaks`.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding code here places
    arrays with ``NamedSharding``/``shard_map`` and lets the compiler
    propagate the rest, which Explicit axes (jax's default) reject."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over CPU host devices (tests w/ XLA_FLAGS device_count)."""
    return make_mesh((data, model), ("data", "model"))
