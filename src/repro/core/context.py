"""Synkhronos execution context: mesh construction and global state.

The paper's ``synk.fork()`` spawned one Python process per GPU and used
barriers for synchronization.  Under XLA SPMD there is a single program and
synchronization is structural, so ``fork`` builds a ``jax.sharding.Mesh``
instead.  The mesh axes play the role of the paper's workers:

* ``data`` axes  — the paper's data-parallel workers (scatter/reduce axes).
* ``model`` axis — tensor/expert/sequence parallel groups (beyond-paper).
* ``pod`` axis   — the outermost data-parallel axis across pods.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

_CURRENT: "SynkContext | None" = None

# Axes that scatter/reduce operate over, in nesting order. Every axis name in
# a mesh that appears in this tuple is treated as data-parallel.
DATA_AXIS_CANDIDATES = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class SynkContext:
    """Holds the mesh and the split between data-parallel and model axes."""

    mesh: Mesh
    data_axes: tuple[str, ...]
    model_axes: tuple[str, ...]

    @property
    def n_data(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes], dtype=np.int64)) if self.data_axes else 1

    @property
    def n_model(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.model_axes], dtype=np.int64)) if self.model_axes else 1

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def data_spec(self, *trailing: str | None) -> P:
        """PartitionSpec scattering the leading axis over all data axes."""
        return P(self.data_axes, *trailing)

    def replicated_spec(self) -> P:
        return P()

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with explicit Auto axis types (GSPMD propagation)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
    )


def fork(
    mesh_shape: Sequence[int] | None = None,
    axes: Sequence[str] | None = None,
    *,
    data_axes: Sequence[str] | None = None,
    mesh: Mesh | None = None,
) -> SynkContext:
    """Initialise the Synkhronos context (paper: ``synk.fork()``).

    With no arguments, uses every local device on a single ``data`` axis —
    the direct analogue of the paper's "automatically uses all GPUs".
    """
    global _CURRENT
    if mesh is None:
        if mesh_shape is None:
            n = jax.device_count()
            mesh_shape, axes = (n,), ("data",)
        if axes is None:
            raise ValueError("axes must be given when mesh_shape is")
        mesh = make_mesh(mesh_shape, axes)
    if data_axes is None:
        data_axes = tuple(a for a in mesh.axis_names if a in DATA_AXIS_CANDIDATES)
        if not data_axes:  # single unnamed-purpose mesh: treat every axis as data
            data_axes = tuple(mesh.axis_names)
    model_axes = tuple(a for a in mesh.axis_names if a not in data_axes)
    ctx = SynkContext(mesh=mesh, data_axes=tuple(data_axes), model_axes=model_axes)
    _CURRENT = ctx
    return ctx


def current() -> SynkContext:
    if _CURRENT is None:
        return fork()
    return _CURRENT


def reset() -> None:
    """Drop the global context (tests)."""
    global _CURRENT
    _CURRENT = None
