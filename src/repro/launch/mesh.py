"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set XLA_FLAGS
before jax initializes, and smoke tests must see exactly 1 CPU device.

Axis roles (DESIGN.md §4):
  pod    — slowest hop (inter-pod). Carries only gradient/MoE collectives.
  data   — intra-pod DP/FSDP axis.
  model  — fastest hop (intra-pod ICI ring): TP/SP/EP axis.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def _auto(n: int) -> tuple:
    """All-Auto axis types: the partitioner (GSPMD/shard_map) places
    everything not pinned by a sharding annotation."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh for tests/benchmarks (e.g. (8,), ('data',) on 8 host
    devices)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


GRID_AXES = ("rows", "cols")
GRID_AXES_3D = ("planes", "rows", "cols")


def make_grid_mesh(*shape: int, axes: Optional[Tuple[str, ...]] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """N-D process mesh for hierarchical domain decomposition (the HDOT
    partition scheme applied on every grid dim at process level; the halo
    machinery reuses the same scheme for its task-level chunk grid).

    ``make_grid_mesh(rows, cols)`` is the 2-D (rows x cols) mesh;
    ``make_grid_mesh(planes, rows, cols)`` the 3-D mesh HPCCG's native grid
    decomposes onto. Size-1 axes keep the full N-D code path alive on lower-
    dimensional layouts — (4, 1) and (1, 4) are the slab topologies expressed
    in the 2-D scheme, (4, 2, 1) a 2-D topology in the 3-D scheme — so
    benchmarks can track topology gaps on equal footing. ``devices``
    (default: all of ``jax.devices()``) picks the devices the mesh spans,
    e.g. one device of a multi-chip host for a single-device reference."""
    if axes is None:
        if len(shape) not in (2, 3):
            raise ValueError(f"make_grid_mesh default axes cover 2-D/3-D "
                             f"grids; got shape {shape} — pass axes=")
        axes = GRID_AXES if len(shape) == 2 else GRID_AXES_3D
    if len(axes) != len(shape):
        raise ValueError(f"mesh shape {shape} and axes {axes} disagree")
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=_auto(len(shape)),
                         devices=devices)


def make_single_device_mesh(axes: Tuple[str, ...] = ("data", "model")) -> Mesh:
    """1-device mesh with the production axis names: lets the full sharded
    code path run on one CPU device (every axis has size 1)."""
    return jax.make_mesh((1,) * len(axes), axes, axis_types=_auto(len(axes)))


def describe(mesh: Mesh) -> str:
    return " x ".join(
        f"{n}={s}" for n, s in zip(mesh.axis_names, mesh.devices.shape))


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def validate_production_mesh(mesh: Mesh, *, multi_pod: bool) -> None:
    # a validator that compiles away under `python -O` validates nothing
    want = (2, 16, 16) if multi_pod else (16, 16)
    if tuple(mesh.devices.shape) != want:
        raise ValueError(f"production mesh must be {want}, "
                         f"got {tuple(mesh.devices.shape)}")
    if mesh.devices.size != (512 if multi_pod else 256):
        raise ValueError(f"production mesh has {mesh.devices.size} devices")
