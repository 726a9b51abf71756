"""Mesh / shard_map helpers.

Every mesh / shard_map / axis-size use in the tree goes through this
module, so the mesh conventions (Auto axis types, explicit device
slices, the dp/model axis names) live in one place.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` are the *manual* axes (None =
    all mesh axes manual), the rest stay auto (GSPMD)."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


def make_mesh(shape: Sequence[int], axis_names: Sequence[str]
              ) -> jax.sharding.Mesh:
    """jax.make_mesh with Auto axis types pinned and the device list
    sliced explicitly (the first ``prod(shape)`` devices)."""
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"mesh {tuple(shape)} needs {n} devices, "
                         f"only {len(devices)} available")
    return jax.make_mesh(
        tuple(shape), tuple(axis_names), devices=devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(tuple(shape)))


def axis_size(axis_name) -> int:
    """Static size of a (possibly composite) mesh axis inside shard_map."""
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return math.prod(jax.lax.axis_size(a) for a in names)


# ----------------------------------------------------------------------
# Ambient mesh registry: model code (e.g. the MoE expert-parallel island)
# needs the mesh to open shard_map regions inside a jitted step.  When no
# mesh is set (single-device smoke tests), layers fall back to local-only
# implementations.
# ----------------------------------------------------------------------

_GLOBAL_MESH: jax.sharding.Mesh | None = None


def set_global_mesh(mesh: jax.sharding.Mesh | None) -> None:
    global _GLOBAL_MESH
    _GLOBAL_MESH = mesh


def get_global_mesh() -> jax.sharding.Mesh | None:
    return _GLOBAL_MESH


def dp_axes(mesh: jax.sharding.Mesh | None = None) -> tuple[str, ...]:
    """Data-parallel axes of the production meshes ('pod' composes)."""
    mesh = mesh or _GLOBAL_MESH
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


MODEL_AXIS = "model"
POD_AXIS = "pod"
