"""HPCCG (Mantevo; HDOT paper §4.3): the timed path, its plain reference
and its required work.

The timed path is ``repro.core.stencil.hpccg_solve(..., mode="hdot")`` with
the program's defaults: ``max_iter`` iterations of unpreconditioned CG on
the 27-point operator (``diagonal`` on the diagonal, -1 to each of the 26
neighbours, Dirichlet-0 outside the global grid), over a grid decomposed on
a (planes, rows, cols) mesh. Each iteration runs the matvec through the
chained face exchange and the hdot interior chunks, two ``_ddot``
reductions (chunk partials, then an allreduce over the mesh) and three
vector updates. The reference shares no code with the program: a jnp CG on
the global array, which XLA partitions over the same chips.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stencil import hpccg_solve
from repro.launch.mesh import GRID_AXES_3D, make_grid_mesh

import generate


def make_mesh(shape, devices):
    if len(shape) != 3:
        raise ValueError(f"hpccg decomposes on a (planes, rows, cols) mesh, "
                         f"got {shape}")
    return make_grid_mesh(*shape, devices=devices)


def global_shape(cfg: dict, mesh) -> tuple:
    """Weak scaling, as Mantevo runs it: every chip holds ``local_grid``."""
    return tuple(n * m for n, m in zip(cfg["local_grid"], mesh.devices.shape))


def make_input(cfg: dict, traffic: dict, mesh, key) -> jax.Array:
    """The right-hand side b."""
    return generate.draw(key, global_shape(cfg, mesh), jnp.dtype(cfg["dtype"]),
                         traffic["input"], NamedSharding(mesh, P(*GRID_AXES_3D)))


def solve(cfg: dict, mesh, b):
    """One solve from x = 0: (x after ``max_iter`` iterations, residual norm
    after each)."""
    if cfg["tolerance"] != 0.0:
        raise ValueError("the program runs a fixed iteration count; only "
                         "tolerance 0 is its semantics")
    return hpccg_solve(b, mesh, GRID_AXES_3D, cfg["max_iter"], mode="hdot")


def _matvec(p, diagonal: float):
    """The 27-point operator: ``diagonal`` times the cell minus its 26
    neighbours, zero outside the grid."""
    q = jnp.pad(p, 1)
    n0, n1, n2 = p.shape
    neighbours = sum(q[1 + i:1 + i + n0, 1 + j:1 + j + n1, 1 + k:1 + k + n2]
                     for i in (-1, 0, 1) for j in (-1, 0, 1) for k in (-1, 0, 1)
                     if (i, j, k) != (0, 0, 0))
    return diagonal * p - neighbours


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _cg(b, iters: int, diagonal: float, dtype):
    b = b.astype(dtype)

    def it(carry, _):
        x, r, p, rr = carry
        ap = _matvec(p, diagonal)
        alpha = rr / jnp.sum(p * ap)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.sum(r * r)
        p = r + (rr_new / rr) * p
        return (x, r, p, rr_new), jnp.sqrt(rr_new)

    init = (jnp.zeros_like(b), b, b, jnp.sum(b * b))
    (x, _, _, _), hist = lax.scan(it, init, None, length=iters)
    return x, hist


def reference(cfg: dict, mesh, b, dtype=jnp.float32):
    """Plain CG from x = 0, computed in `dtype`: x after ``max_iter``
    iterations and the residual norm after each."""
    return _cg(b, cfg["max_iter"], float(cfg["diagonal"]), jnp.dtype(dtype))


@jax.jit
def _errors(x, hist, x_ref, hist_ref):
    x, x_ref = x.astype(jnp.float32), x_ref.astype(jnp.float32)
    hist, hist_ref = hist.astype(jnp.float32), hist_ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(x - x_ref)) / jnp.max(jnp.abs(x_ref)),
            jnp.max(jnp.abs(hist - hist_ref) / jnp.abs(hist_ref)))


def compare(out, ref) -> dict:
    """``x_err``: the largest error of a component of x over the largest
    reference component; ``resid_err``: the largest relative error of an
    iteration's residual norm."""
    x_err, resid_err = _errors(out[0], out[1], ref[0], ref[1])
    return {"x_err": float(x_err), "resid_err": float(resid_err)}


def work(cfg: dict) -> dict:
    """Operations and HBM bytes one solve needs on one chip.

    Bytes, in passes over one vector of the local grid: CG keeps three
    vectors (x, r, p) that all change in every iteration, so each is read
    and written once: 6 passes. The two dot products are global
    synchronisation points: alpha needs p.Ap over the whole grid before x
    and r can change, and beta needs r.r before p can. So an iteration needs
    a second sweep before its updating one, which reads r and the old p to
    form p and A p on the fly: 2 passes. A p is recomputed in the updating
    sweep rather than stored, which costs operations and no bytes. Least:
    8 passes per iteration, plus 2 to start (b.b, and x = 0). Operations
    per cell: 27 for the matvec (a multiply and 26 subtractions), 2 for
    each of the two dot products and 2 for each of the three vector
    updates: 37 per iteration, plus 2 for b.b. At about one operation per
    byte the operation bound lies far below the byte bound."""
    cells = math.prod(cfg["local_grid"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    iters = cfg["max_iter"]
    return {"flops": cells * (2 + 37 * iters),
            "bytes": item * cells * (2 + 8 * iters)}
