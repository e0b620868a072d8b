"""HPCCG's 27-point matvec in box form, A p = 27·p − W(p) with W the 3×3×3
window sum, against a 27-slice oracle kept here: the chained hdot matvec
(interior window sum, face sums of the received z planes, assemble), the
two-phase matvec and ``_sum27`` itself, on 1x1x1, 1x2x2 and 2x2x2 meshes
(and a (rows, cols) pair, and z slabs on 1 and 4 devices, which run the
same chain with one axis), local blocks of 8×8×8 and an odd 6×10×12, with
the edge and corner cells of every block checked on their own.

The multi-device cases run in one child process on forced host devices
(``python tests/test_hpccg_matvec.py`` prints their outputs)."""
from __future__ import annotations

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import stencil
from repro.launch.mesh import GRID_AXES, GRID_AXES_3D, make_grid_mesh

# (2, 2): (rows, cols); (1,) and (4,): z slabs
MESHES = [(1, 1, 1), (1, 2, 2), (2, 2, 2), (2, 2), (1,), (4,)]
AXES = {1: ("z",), 2: GRID_AXES, 3: GRID_AXES_3D}
LOCALS = [(8, 8, 8), (6, 10, 12)]
MODES = ["hdot", "two_phase"]
CASES = list(itertools.product(MESHES, LOCALS, MODES))
MULTI = [c for c in CASES if np.prod(c[0]) > 1]
RTOL, ATOL = 1e-6, 1e-5


def _case_id(case):
    mesh, local, mode = case
    return (f"{'x'.join(map(str, mesh))}-{'x'.join(map(str, local))}-{mode}")


def sum27_slices(q: np.ndarray) -> np.ndarray:
    """The 27-point operator (diagonal 26, off-diagonal −1) as 27 shifted
    slices of a fully padded block, in float64."""
    q = np.asarray(q, np.float64)
    nx, ny, nz = q.shape[0] - 2, q.shape[1] - 2, q.shape[2] - 2
    acc = np.zeros((nx, ny, nz))
    for dx, dy, dz in itertools.product((-1, 0, 1), repeat=3):
        sl = q[1 + dx:nx + 1 + dx, 1 + dy:ny + 1 + dy, 1 + dz:nz + 1 + dz]
        acc += 26.0 * sl if dx == dy == dz == 0 else -sl
    return acc


def _global_p(mesh_shape, local) -> np.ndarray:
    split = (1,) * (3 - len(mesh_shape)) + tuple(mesh_shape)
    shape = tuple(n * m for n, m in zip(local, split))
    return np.asarray(jax.random.normal(jax.random.PRNGKey(16), shape,
                                        jnp.float32))


def chain_matvec(mesh_shape, local, mode) -> np.ndarray:
    """A p through `_stencil27_matvec_chain` on a mesh of `mesh_shape`."""
    axes = AXES[len(mesh_shape)]
    mesh = make_grid_mesh(*mesh_shape, axes=axes,
                          devices=jax.devices()[:int(np.prod(mesh_shape))])
    dims = tuple(range(3 - len(axes), 3))
    spec = P(*((None,) * (3 - len(axes)) + axes))
    f = jax.jit(jax.shard_map(
        lambda q: stencil._stencil27_matvec_chain(q, axes, dims, mode),
        mesh=mesh, in_specs=spec, out_specs=spec))
    return np.asarray(f(_global_p(mesh_shape, local)))


def _block_edges(shape, local) -> tuple:
    """Index arrays of the cells on a local block's first or last plane
    along any dim (the cells whose neighbours cross a block boundary), and
    of the block corners (first or last along every dim)."""
    at_edge = [np.isin(np.arange(n) % b, (0, b - 1)) for n, b in
               zip(shape, local)]
    grids = np.meshgrid(*at_edge, indexing="ij")
    edge = grids[0] | grids[1] | grids[2]
    corner = grids[0] & grids[1] & grids[2]
    return np.nonzero(edge), np.nonzero(corner)


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_matvec_matches_27_slice_oracle(case, request):
    mesh_shape, local, mode = case
    if case in MULTI:
        got = np.asarray(request.getfixturevalue("child_results")[_case_id(case)])
    else:
        got = chain_matvec(mesh_shape, local, mode)
    p = _global_p(mesh_shape, local)
    want = sum27_slices(np.pad(p, 1))
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    split = (1,) * (3 - len(mesh_shape)) + tuple(mesh_shape)
    blocks = tuple(n // m for n, m in zip(p.shape, split))
    edge, corner = _block_edges(p.shape, blocks)
    assert len(corner[0]) == 8 * int(np.prod(split))
    for idx in (edge, corner):
        np.testing.assert_allclose(got[idx], want[idx], rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("local", LOCALS, ids=lambda s: "x".join(map(str, s)))
def test_sum27_box_form_matches_27_slices(local):
    q = jax.random.normal(jax.random.PRNGKey(27),
                          tuple(n + 2 for n in local), jnp.float32)
    got = np.asarray(jax.jit(stencil._sum27)(q))
    want = sum27_slices(q)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    edge, corner = _block_edges(got.shape, local)
    for idx in (edge, corner):
        np.testing.assert_allclose(got[idx], want[idx], rtol=RTOL, atol=ATOL)


if __name__ == "__main__":
    print(json.dumps({_case_id(c): chain_matvec(*c).tolist() for c in MULTI}))
