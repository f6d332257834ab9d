"""Mesh construction for the production pods and local development.

``make_production_mesh`` is a FUNCTION (not module-level state) so that
importing this module never touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and then calls it.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mk(shape, axes) -> Mesh:
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data x model).
    Multi-pod: 2x16x16 = 512 chips (pod x data x model); the ``pod`` axis
    is the cross-pod (DCN) data-parallel axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def single_device_mesh() -> Mesh:
    """All production axes present with size 1 — used by CPU smoke tests so
    every PartitionSpec in the model code resolves."""
    return _mk((1, 1, 1), ("pod", "data", "model"))


def local_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Development mesh over however many local devices exist."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return _mk((data, model), ("data", "model"))
