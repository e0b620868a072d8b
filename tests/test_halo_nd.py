"""N-D decomposition machinery, single-device (the 2x2x2 / 4x2x1 real-mesh
equivalences live in test_system.py). The safety property is the same at
every depth of the hierarchy: every schedule/knob/topology must be
numerically identical to the two-phase oracle — including the corner and
edge cells, which the corner-free exchange must still get right for star
stencils on all three axes at once."""
from __future__ import annotations

import functools
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.domain import interior_boxes
from repro.core.halo import (exchange_halo_nd, halo_scan_nd,
                             pad_with_halo_nd, stencil_apply_nd,
                             stencil_with_halo_nd)

AXES3 = ("planes", "rows", "cols")
DECOMP3 = tuple(zip(AXES3, (0, 1, 2)))


@pytest.fixture(scope="module")
def grid_mesh3():
    from repro.launch.mesh import make_grid_mesh

    return make_grid_mesh(1, 1, 1)


def _star3_fn(width: int):
    """Separable 3-D star stencil of `width` (reads the full 3-axis cross,
    never a corner). Input padded by `width` on all three dims; returns the
    un-padded update. The sum is scaled by a power of two, so the product
    is exact and an FMA rounds no differently: schedules can then be
    compared bit for bit."""
    scale = 0.5 ** (3 * (2 * width + 1)).bit_length()
    def fn(p):
        w = width
        n0, n1, n2 = (s - 2 * w for s in p.shape)
        acc = 0.0
        for d in range(-w, w + 1):
            acc = (acc
                   + p[w + d:w + d + n0, w:w + n1, w:w + n2]
                   + p[w:w + n0, w + d:w + d + n1, w:w + n2]
                   + p[w:w + n0, w:w + n1, w + d:w + d + n2])
        return acc * scale
    return fn


def _shmap(fn, mesh):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(*AXES3),),
                                 out_specs=P(*AXES3)))


def test_interior_boxes_partition_3d():
    """The task-level chunk grid tiles exactly the interior of the block —
    the process partition scheme applied one level down, in 3-D."""
    shape, w, grid = (13, 11, 9), 2, (3, 2, 2)
    boxes = interior_boxes(shape, w, grid)
    assert len(boxes) == 12
    cells = set()
    for b in boxes:
        for idx in itertools.product(*(range(a, o) for a, o in
                                       zip(b.start, b.stop))):
            assert idx not in cells
            cells.add(idx)
    want = set(itertools.product(*(range(w, s - w) for s in shape)))
    assert cells == want


# (block, dtype, decomposed dims, width, chunks a dim, weights, the tile
# quantum each dim must be cut on: 1 where the block keeps the cut of
# [width, n - width)).
CUT_CASES = [
    pytest.param((64, 512), jnp.float32, (0, 1), 1, (3, 2), None, (8, 128),
                 id="2d-f32"),
    pytest.param((96, 512), jnp.bfloat16, (0, 1), 1, (3, 2), None, (16, 128),
                 id="2d-bf16"),
    pytest.param((64, 512), jnp.bfloat16, (0, 1), 1, (3, 2), None, (1, 128),
                 id="2d-bf16-rows-too-few-tiles"),
    pytest.param((64, 512), jnp.float32, (0, 1), 2, (3, 2), None, (8, 128),
                 id="2d-f32-width2"),
    pytest.param((16384, 16384), jnp.float32, (0, 1), 1, (4, 4), None,
                 (8, 128), id="2d-f32-16k"),
    pytest.param((64, 512), jnp.float32, (0, 1), 1, (3, 2),
                 ((2, 9, 51), (300, 210)), (8, 128), id="2d-f32-uneven-cut"),
    pytest.param((64, 512), jnp.float32, (0,), 1, (4,), None, (8,),
                 id="slab-f32"),
    pytest.param((6, 32, 384), jnp.float32, (0, 1, 2), 1, (2, 2, 1), None,
                 (1, 8, 128), id="3d-f32"),
    pytest.param((17, 13), jnp.float32, (0, 1), 1, (3, 2), None, (1, 1),
                 id="small-fallback"),
    pytest.param((11, 9, 13), jnp.float32, (0, 1, 2), 1, (2, 2, 1),
                 ((6, 3), None, (0, 4, 7)), (1, 1, 1),
                 id="small-fallback-uneven-cut"),
]


@pytest.mark.parametrize("block,dtype,dims,width,ks,weights,quanta",
                         CUT_CASES)
def test_task_cut_tiles_block(block, dtype, dims, width, ks, weights, quanta):
    """halo_scan_nd's task partition: the faces and chunk boxes tile the
    block exactly once; on a tiled dim every chunk starts and ends on a
    multiple of the tile and each face is at least `width` thick; the chunk
    count is the grid's, uneven cut or not; where the block is too small
    the chunks are exactly `interior_boxes`' cut of [width, n - width)."""
    from repro.core.domain import face_boxes
    from repro.core.halo import _task_cut

    u = jax.ShapeDtypeStruct(block, dtype)
    ext = [block[d] for d in dims]
    spans, faces, chunks = _task_cut(u, dims, width, ks, weights)
    counts = [k if wd is None else len(wd)
              for k, wd in zip(ks, weights or [None] * len(ks))]
    assert len(faces) == 2 * len(dims)
    assert len(chunks) == int(np.prod(counts))
    for (lo, hi), n, q in zip(spans, ext, quanta):
        assert lo % q == 0 and hi % q == 0 and lo >= width and n - hi >= width
        assert (q == 1) == ((lo, hi) == (width, n - width))
    for b in chunks:
        assert all(a % q == 0 and o % q == 0
                   for a, o, q in zip(b.start, b.stop, quanta))
    if set(quanta) == {1}:
        assert chunks == interior_boxes(ext, width, counts, weights)
    assert faces == face_boxes(ext, spans)
    # each cell of the decomposed dims in exactly one box: the box sizes sum
    # to the block's, and no two boxes overlap
    boxes = faces + [b for b in chunks if b.size]
    assert sum(b.size for b in boxes) == int(np.prod(ext))
    for i, a in enumerate(boxes):
        for b in boxes[i + 1:]:
            assert any(a.stop[d] <= b.start[d] or b.stop[d] <= a.start[d]
                       for d in range(len(ext))), (a, b)


@pytest.mark.parametrize("subdomains", [(1, 1, 1), (2, 2, 2), (3, 2, 1), 2,
                                        (8, 8, 8)])
@pytest.mark.parametrize("periodic", [False, True])
def test_stencil_hdot_nd_matches_two_phase(grid_mesh3, subdomains, periodic):
    """The 3-D chunk-grid knob must not change numerics for any grainsize."""
    u = jax.random.normal(jax.random.PRNGKey(0), (16, 14, 12), jnp.float32)
    fn = _star3_fn(1)
    want = _shmap(lambda x: stencil_apply_nd(
        x, fn, DECOMP3, 1, periodic, "two_phase"), grid_mesh3)(u)
    got = _shmap(lambda x: stencil_apply_nd(
        x, fn, DECOMP3, 1, periodic, "hdot", subdomains), grid_mesh3)(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def scan_matches_iterated(mesh_shape, mode, width, block, steps, peel,
                          weights):
    """halo_scan_nd against `steps` iterated two-phase 3-D applies on a
    (planes, rows, cols) mesh of `mesh_shape`, each chip holding `block`:
    are the grids, and the per-step max |new - old|, equal bit for bit?"""
    from repro.launch.mesh import make_grid_mesh

    mesh = make_grid_mesh(*mesh_shape,
                          devices=jax.devices()[:int(np.prod(mesh_shape))])
    u = jax.random.normal(jax.random.PRNGKey(1),
                          tuple(b * m for b, m in zip(block, mesh_shape)),
                          jnp.float32)
    fn = _star3_fn(width)

    def change(new, old):
        return jnp.max(jnp.abs(new - old))

    def scan(x):
        return halo_scan_nd(x, fn, DECOMP3, width, steps, periodic=True,
                            mode=mode, subdomains=(2, 2, 1),
                            partial_fn=change, peel=peel, weights=weights)

    def iterate(x):
        hist = []
        for _ in range(steps):
            new = stencil_apply_nd(x, fn, DECOMP3, width, True, "two_phase")
            hist.append(jax.lax.pmax(change(new, x), AXES3))
            x = new
        return x, jnp.stack(hist)

    specs = dict(mesh=mesh, in_specs=(P(*AXES3),),
                 out_specs=(P(*AXES3), P()))
    got = jax.jit(jax.shard_map(scan, **specs))(u)
    want = jax.jit(jax.shard_map(iterate, **specs))(u)
    return [bool(np.array_equal(np.asarray(g), np.asarray(w)))
            for g, w in zip(got, want)]


# (mesh, mode, width, block, steps, peel, weights). The first six keep the
# ids they had as a product of (width, block) and mode; the rest cover odd
# and even step counts, the unpeeled drain, an uneven interior cut (with an
# empty chunk) and a real 2x2x2 exchange.
SCAN_CASES = [
    pytest.param((1, 1, 1), mode, width, block, 3, True, None,
                 id=f"{width}-shape{i}-{mode}")
    for i, (width, block) in enumerate([(1, (11, 9, 13)), (1, (12, 10, 8)),
                                        (2, (13, 11, 10))])
    for mode in ("hdot", "two_phase")]
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (11, 9, 13), steps, peel, None,
                 id=f"{'x'.join(map(str, mesh))}-steps{steps}-"
                    f"{'peeled' if peel else 'unpeeled'}")
    for mesh in ((1, 1, 1), (2, 2, 2)) for steps in (1, 2, 3, 5)
    for peel in (True, False)]
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (11, 9, 13), 3, True,
                 ((6, 3), None, (0, 4, 7)),
                 id=f"{'x'.join(map(str, mesh))}-uneven-cut")
    for mesh in ((1, 1, 1), (2, 2, 2))]
# A block large enough for the tile-aligned cut of its last two dims (8
# rows, 128 lanes): faces a tile thick, chunks on tiles.
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (6, 32, 384), steps, peel, None,
                 id=f"{'x'.join(map(str, mesh))}-aligned-steps{steps}-"
                    f"{'peeled' if peel else 'unpeeled'}")
    for mesh in ((1, 1, 1), (2, 2, 2)) for steps in (1, 2, 3)
    for peel in (True, False)]


@pytest.mark.parametrize("mesh_shape,mode,width,block,steps,peel,weights",
                         SCAN_CASES)
def test_halo_scan_nd_equals_iterated_apply(mesh_shape, mode, width, block,
                                            steps, peel, weights, request):
    """halo_scan_nd(steps=k) == k iterated two-phase 3-D applies, bit for
    bit, grid and per-step residual: odd AND even extents and step counts,
    both schedules, peeled or not, uniform or uneven interior cut; the
    2x2x2 cases run in one child process on forced host devices."""
    if np.prod(mesh_shape) > 1:
        found = request.getfixturevalue("child_results")[request.node.callspec.id]
    else:
        found = scan_matches_iterated(mesh_shape, mode, width, block, steps,
                                      peel, weights)
    assert found == [True, True]


def test_stencil_with_halo_nd_uses_given_halos():
    """Pre-exchanged face halos (random, not wrap-around) flow into the
    right cells — including every edge/corner region, via the corner-free
    face assembly."""
    k = jax.random.PRNGKey(2)
    u = jax.random.normal(k, (12, 10, 14), jnp.float32)
    halos = []
    for d, s in enumerate(u.shape):
        shp = list(u.shape)
        shp[d] = 1
        halos.append(
            (jax.random.normal(jax.random.fold_in(k, 2 * d + 1), shp),
             jax.random.normal(jax.random.fold_in(k, 2 * d + 2), shp)))
    fn = _star3_fn(1)
    got = jax.jit(functools.partial(stencil_with_halo_nd, stencil_fn=fn,
                                    width=1, dims=(0, 1, 2),
                                    subdomains=(2, 1, 3)))(u, halos)
    want = fn(pad_with_halo_nd(u, halos, 1, (0, 1, 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_exchange_halo_nd_periodic_wraps_own_edges(grid_mesh3):
    """Size-1 axes: periodic wraps each dim's own edges (the N-D analogue of
    the 1-D single-rank contract)."""
    u = jnp.arange(2.0 * 3 * 4).reshape(2, 3, 4)

    def ex(x):
        halos = exchange_halo_nd(x, DECOMP3, 1, periodic=True)
        return tuple(h for pair in halos for h in pair)

    out = jax.jit(jax.shard_map(
        ex, mesh=grid_mesh3, in_specs=(P(*AXES3),),
        out_specs=tuple(P(*AXES3) for _ in range(6))))(u)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(u[-1:]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(u[:1]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(u[:, -1:]))
    np.testing.assert_array_equal(np.asarray(out[4]),
                                  np.asarray(u[:, :, -1:]))


def test_rk3_2d_mesh_matches_slab(grid_mesh3):
    """rk3_solve on a 1x1 (rows, cols) topology == the z-slab solver, both
    schedules (stage-carried halos of the 5-component state on BOTH axes)."""
    from repro.core.stencil import rk3_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh
    from tests.euler_reference import random_state

    u0 = random_state(jax.random.PRNGKey(3), (12, 20, 32))
    want, dt_want = rk3_solve(u0, make_mesh((1,), ("data",)), ("data",), 4,
                              mode="two_phase")
    for mode in ("two_phase", "hdot"):
        got, dt = rk3_solve(u0, make_grid_mesh(1, 1), ("rows", "cols"), 4,
                            mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(dt), np.asarray(dt_want),
                                   rtol=1e-6)


def test_hpccg_3d_mesh_matches_slab(grid_mesh3):
    """CG on the full (x, y, z) topology converges identically to the z-slab
    solver — exercises the chained sequential exchange end to end."""
    from repro.core.stencil import hpccg_solve
    from repro.launch.mesh import make_mesh

    b = jax.random.normal(jax.random.PRNGKey(4), (10, 12, 12), jnp.float32)
    _, h_want = hpccg_solve(b, make_mesh((1,), ("data",)), ("data",), 15,
                            mode="two_phase")
    for mode in ("two_phase", "hdot"):
        _, h = hpccg_solve(b, grid_mesh3, AXES3, 15, mode=mode)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_want),
                                   rtol=1e-4)


if __name__ == "__main__":
    print(json.dumps({c.id: scan_matches_iterated(*c.values)
                      for c in SCAN_CASES if np.prod(c.values[0]) > 1}))
