"""Heat2D (HDOT paper §4.1): the timed path, its plain reference and its
required work.

The timed path is ``repro.core.stencil.heat2d_solve(..., mode="hdot")``
with the program's defaults: ``sweeps`` 5-point Jacobi sweeps with
Dirichlet-0 edges over a grid block-decomposed on a (rows, cols) mesh, each
chip's block over-decomposed into boundary faces and interior chunks, and
the per-sweep residual max|u_new - u| reduced over the chunks and the mesh.
The reference shares no code with the program: a jnp Jacobi on the global
array, which XLA partitions over the same chips.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stencil import heat2d_solve
from repro.launch.mesh import GRID_AXES, make_grid_mesh

import generate


def make_mesh(shape, devices):
    if len(shape) != 2:
        raise ValueError(f"heat2d decomposes on a (rows, cols) mesh, got {shape}")
    return make_grid_mesh(*shape, devices=devices)


def global_shape(cfg: dict, mesh) -> tuple:
    """Weak scaling: every chip holds a block of ``local_grid``."""
    return tuple(n * m for n, m in zip(cfg["local_grid"], mesh.devices.shape))


def make_input(cfg: dict, traffic: dict, mesh, key) -> jax.Array:
    return generate.draw(key, global_shape(cfg, mesh), jnp.dtype(cfg["dtype"]),
                         traffic["input"], NamedSharding(mesh, P(*GRID_AXES)))


def solve(cfg: dict, mesh, u):
    """One solve: (grid after ``sweeps`` sweeps, residual per sweep)."""
    return heat2d_solve(u, mesh, GRID_AXES, cfg["sweeps"], mode="hdot")


def carry(out):
    """A time-stepper continues from the grid the last solve reached."""
    return out[0]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _jacobi(u0, sweeps: int, dtype):
    def sweep(u, _):
        p = jnp.pad(u, 1)
        new = 0.25 * (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:])
        return new, jnp.max(jnp.abs(new - u))

    return lax.scan(sweep, u0.astype(dtype), None, length=sweeps)


def reference(cfg: dict, mesh, u, dtype=jnp.float32):
    """Plain Jacobi with Dirichlet-0 edges, computed in `dtype`: the grid
    after ``sweeps`` sweeps and the residual of each sweep."""
    return _jacobi(u, cfg["sweeps"], jnp.dtype(dtype))


@jax.jit
def _errors(u, hist, u_ref, hist_ref):
    u, u_ref = u.astype(jnp.float32), u_ref.astype(jnp.float32)
    hist, hist_ref = hist.astype(jnp.float32), hist_ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(u - u_ref)) / jnp.max(jnp.abs(u_ref)),
            jnp.max(jnp.abs(hist - hist_ref) / jnp.abs(hist_ref)))


def compare(out, ref) -> dict:
    """``grid_err``: the largest cell error over the largest reference cell;
    ``resid_err``: the largest relative error of a sweep's residual."""
    grid, resid = _errors(out[0], out[1], ref[0], ref[1])
    return {"grid_err": float(grid), "resid_err": float(resid)}


def work(cfg: dict) -> dict:
    """Operations and HBM bytes one solve needs on one chip.

    Bytes: a sweep-by-sweep Jacobi must read every cell of the old grid and
    write every cell of the new one once per sweep, since every cell changes
    (``2 * itemsize`` per cell per sweep, 8 B in float32); a neighbour's
    value is read by the same pass, so it adds nothing. An implementation
    that fuses several sweeps into one pass over HBM (temporal blocking)
    could move less: the count is the bound of the per-sweep algorithm.
    Operations: 3 adds and 1 multiply for the update, and a subtract, an
    absolute value and a max for the residual: 7 per cell per sweep. At 7/8
    operations per byte the operation bound lies far below the byte bound,
    so the byte bound sets the least time."""
    cells = math.prod(cfg["local_grid"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    return {"flops": 7 * cells * cfg["sweeps"],
            "bytes": 2 * item * cells * cfg["sweeps"]}
