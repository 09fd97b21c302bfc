"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first backend init —
dryrun.py must set XLA_FLAGS before this runs).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_auto_mesh",
    "make_production_mesh",
    "data_axes",
    "coded_workers",
    "coded_axis_size",
    "mesh_devices_for_m",
    "remesh_for_m",
]


def make_auto_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 two-pod (512 chips).

    Axes: 'pod' (DCN boundary — the realistic gradient-coding axis, see
    DESIGN.md §3), 'data' (DP / coded workers / FSDP), 'model' (TP/EP)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """The coded-worker axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a != "model")


def coded_workers(mesh) -> int:
    import numpy as np

    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))


def coded_axis_size(mesh, coding_axes) -> int:
    """Total coded-worker extent of ``mesh`` over explicit ``coding_axes``."""
    import numpy as np

    coding = tuple(coding_axes)
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names if a in coding] or [1]))


def mesh_devices_for_m(mesh, coding_axes, m: int) -> int:
    """Device count a :func:`remesh_for_m` at worker count ``m`` would need:
    one per coded worker times the mesh's non-coding extent (TP stays)."""
    import numpy as np

    coding = tuple(coding_axes)
    non_coding = int(
        np.prod([s for a, s in zip(mesh.axis_names, mesh.devices.shape) if a not in coding] or [1])
    )
    return int(m) * non_coding


def remesh_for_m(mesh, coding_axes, m: int) -> jax.sharding.Mesh:
    """Re-derive a mesh for a new coded-worker count (elastic rebuild,
    DESIGN.md §13).

    The first coding axis absorbs the full worker count ``m`` and any
    further coding axes collapse to 1 (a membership transition has no
    reason to preserve the old pod split); non-coding axes (e.g. 'model')
    keep their size, so tensor-parallel shards never move.  Devices are
    taken in enumeration order — surviving workers at unchanged mesh
    coordinates keep their device, which is what lets the engine carry
    their buffers across the rebuild instead of round-tripping them
    through the host."""
    coding = tuple(coding_axes)
    if not any(a in coding for a in mesh.axis_names):
        raise ValueError(f"mesh axes {mesh.axis_names} contain no coding axis from {coding}")
    if m < 1:
        raise ValueError(f"worker count must be positive, got m={m}")
    shape, first = [], True
    for a, size in zip(mesh.axis_names, mesh.devices.shape):
        if a in coding:
            shape.append(int(m) if first else 1)
            first = False
        else:
            shape.append(int(size))
    needed = mesh_devices_for_m(mesh, coding_axes, m)
    avail = len(jax.devices())
    if needed > avail:
        raise ValueError(
            f"spmd mesh for m={m} needs {needed} devices "
            f"({needed // int(m)} per coded worker), only {avail} available"
        )
    return make_auto_mesh(tuple(shape), mesh.axis_names)
