"""Mesh construction for single-pod and multi-pod topologies.

The production meshes (assignment):
  single-pod : (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Functions only — importing this module never touches jax device state.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math

import jax

POD_AXIS = "pod"
DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical description of a mesh, independent of physical devices."""

    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def num_devices(self) -> int:
        return math.prod(self.shape)

    def axis_size(self, name: str) -> int:
        if name not in self.axes:
            return 1
        return self.shape[self.axes.index(name)]

    @property
    def dp_size(self) -> int:
        """Total data-parallel degree (pod x data)."""
        return self.axis_size(POD_AXIS) * self.axis_size(DATA_AXIS)

    @property
    def tp_size(self) -> int:
        return self.axis_size(MODEL_AXIS)


SINGLE_POD = MeshSpec(shape=(16, 16), axes=(DATA_AXIS, MODEL_AXIS))
MULTI_POD = MeshSpec(shape=(2, 16, 16), axes=(POD_AXIS, DATA_AXIS, MODEL_AXIS))


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with GSPMD-auto axis types: the sharding rules
    here annotate with ``PartitionSpec`` and let the partitioner fill in
    the rest, which explicit axes (jax's default) would refuse."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def use_mesh(mesh):
    """Context manager putting ``mesh`` in scope (None -> no-op)."""
    return contextlib.nullcontext() if mesh is None else jax.set_mesh(mesh)


def make_mesh(spec: MeshSpec) -> jax.sharding.Mesh:
    """Build a jax Mesh for ``spec`` from the currently visible devices."""
    return make_auto_mesh(spec.shape, spec.axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (uses however many devices are visible)."""
    if pod:
        return make_auto_mesh((pod, data, model),
                              (POD_AXIS, DATA_AXIS, MODEL_AXIS))
    return make_auto_mesh((data, model), (DATA_AXIS, MODEL_AXIS))


def batch_axes(mesh_or_spec) -> tuple[str, ...]:
    """Mesh axes over which the global batch is sharded."""
    axes = mesh_or_spec.axes if hasattr(mesh_or_spec, "axes") else mesh_or_spec.axis_names
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in axes)


def model_axis(mesh_or_spec) -> str:
    return MODEL_AXIS
