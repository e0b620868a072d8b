"""2-D (rows x cols) decomposition machinery, single-device (the 2x2 / 4x1
real-mesh equivalences live in test_system.py). The safety property is the
same as 1-D: every schedule/knob/topology must be numerically identical to
the two-phase oracle — including the corner cells, which a corner-free
exchange must still get right for star stencils."""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.domain import interior_boxes
from repro.core.halo import (halo_scan_nd, pad_with_halo_nd, stencil_apply_nd,
                             stencil_with_halo_nd)

GRID = (("rows", 0), ("cols", 1))


@pytest.fixture(scope="module")
def grid_mesh():
    from repro.launch.mesh import make_grid_mesh

    return make_grid_mesh(1, 1)


def _star_fn(width: int):
    """Separable star stencil of `width` (reads the full cross, no corners).
    Input padded by `width` on both dims; returns the un-padded update. The
    sum is scaled by a power of two, so the product is exact and a compiler
    that fuses a multiply into the next add (an FMA) rounds no differently:
    schedules can then be compared bit for bit."""
    scale = 0.5 ** (2 * (2 * width + 1)).bit_length()
    def fn(p):
        n0, n1 = p.shape[0] - 2 * width, p.shape[1] - 2 * width
        acc = 0.0
        for d in range(-width, width + 1):
            acc = (acc + p[width + d:width + d + n0, width:width + n1]
                   + p[width:width + n0, width + d:width + d + n1])
        return acc * scale
    return fn


def _shmap(fn, mesh):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("rows", "cols"),),
                                 out_specs=P("rows", "cols")))


def test_interior_boxes_partition():
    """The task-level chunk grid tiles exactly the interior of the block."""
    boxes = interior_boxes((17, 13), 2, (3, 2))
    assert len(boxes) == 6
    cells = set()
    for b in boxes:
        for i in range(b.start[0], b.stop[0]):
            for j in range(b.start[1], b.stop[1]):
                assert (i, j) not in cells
                cells.add((i, j))
    assert cells == {(i, j) for i in range(2, 15) for j in range(2, 11)}


@pytest.mark.parametrize("subdomains", [(1, 1), (2, 2), (3, 2), 4, (16, 16)])
@pytest.mark.parametrize("periodic", [False, True])
def test_stencil_hdot_2d_matches_two_phase(grid_mesh, subdomains, periodic):
    """The 2-D chunk-grid knob must not change numerics for any grainsize."""
    u = jax.random.normal(jax.random.PRNGKey(0), (24, 20), jnp.float32)
    fn = _star_fn(1)
    want = _shmap(lambda x: stencil_apply_nd(
        x, fn, GRID, 1, periodic, "two_phase"), grid_mesh)(u)
    got = _shmap(lambda x: stencil_apply_nd(
        x, fn, GRID, 1, periodic, "hdot", subdomains), grid_mesh)(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def scan_matches_iterated(mesh_shape, mode, width, block, steps, peel,
                          weights):
    """halo_scan_nd (uniform or with an explicit cut) against `steps`
    iterated two-phase applies on a (rows, cols) mesh of `mesh_shape`, each
    chip holding `block`: are the grids, and the per-step max |new - old|,
    equal bit for bit?"""
    from repro.launch.mesh import make_grid_mesh

    mesh = make_grid_mesh(*mesh_shape,
                          devices=jax.devices()[:int(np.prod(mesh_shape))])
    u = jax.random.normal(jax.random.PRNGKey(1),
                          tuple(b * m for b, m in zip(block, mesh_shape)),
                          jnp.float32)
    fn = _star_fn(width)
    axes = ("rows", "cols")

    def change(new, old):
        return jnp.max(jnp.abs(new - old))

    def scan(x):
        if weights is None:
            return halo_scan_nd(x, fn, GRID, width, steps, periodic=True,
                                mode=mode, subdomains=(3, 2),
                                partial_fn=change, peel=peel)
        return halo_scan_nd(x, fn, GRID, width, steps, periodic=True,
                            mode=mode, partial_fn=change, peel=peel,
                            weights=weights)

    def iterate(x):
        hist = []
        for _ in range(steps):
            new = stencil_apply_nd(x, fn, GRID, width, True, "two_phase")
            hist.append(jax.lax.pmax(change(new, x), axes))
            x = new
        return x, jnp.stack(hist)

    specs = dict(mesh=mesh, in_specs=(P(*axes),), out_specs=(P(*axes), P()))
    got = jax.jit(jax.shard_map(scan, **specs))(u)
    want = jax.jit(jax.shard_map(iterate, **specs))(u)
    return [bool(np.array_equal(np.asarray(g), np.asarray(w)))
            for g, w in zip(got, want)]


# (mesh, mode, width, block, steps, peel, weights). The first six keep the
# ids they had as a product of (width, block) and mode; the rest cover odd
# and even step counts, the unpeeled drain, an uneven interior cut and a
# real 2x2 exchange.
SCAN_CASES = [
    pytest.param((1, 1), mode, width, block, 4, True, None,
                 id=f"{width}-shape{i}-{mode}")
    for i, (width, block) in enumerate([(1, (17, 13)), (1, (16, 20)),
                                        (2, (21, 18))])
    for mode in ("hdot", "two_phase")]
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (17, 13), steps, peel, None,
                 id=f"{mesh[0]}x{mesh[1]}-steps{steps}-"
                    f"{'peeled' if peel else 'unpeeled'}")
    for mesh in ((1, 1), (2, 2)) for steps in (1, 2, 3, 5)
    for peel in (True, False)]
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (17, 13), 3, True, ((2, 9, 4), (8, 3)),
                 id=f"{mesh[0]}x{mesh[1]}-uneven-cut")
    for mesh in ((1, 1), (2, 2))]
# Blocks large enough for the tile-aligned cut (8 rows, 128 lanes): faces
# a tile thick, chunks on tiles; the uneven cut is snapped to tiles.
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (64, 512), steps, peel, None,
                 id=f"{mesh[0]}x{mesh[1]}-aligned-steps{steps}-"
                    f"{'peeled' if peel else 'unpeeled'}")
    for mesh in ((1, 1), (2, 2)) for steps in (1, 2, 3)
    for peel in (True, False)]
SCAN_CASES += [
    pytest.param(mesh, "hdot", 1, (64, 512), 3, True, ((2, 9, 51), (300, 210)),
                 id=f"{mesh[0]}x{mesh[1]}-aligned-uneven-cut")
    for mesh in ((1, 1), (2, 2))]
SCAN_CASES += [
    pytest.param((1, 1), "hdot", 2, (64, 512), 3, True, None,
                 id="1x1-aligned-width2")]


@pytest.mark.parametrize("mesh_shape,mode,width,block,steps,peel,weights",
                         SCAN_CASES)
def test_halo_scan_2d_equals_iterated_apply(mesh_shape, mode, width, block,
                                            steps, peel, weights, request):
    """halo_scan_nd(steps=k) == k iterated two-phase 2-D applies, bit for
    bit, grid and per-step residual: odd AND even interior sizes and step
    counts, both schedules, peeled or not, uniform or uneven interior cut;
    the 2x2 cases run in one child process on forced host devices."""
    if np.prod(mesh_shape) > 1:
        found = request.getfixturevalue("child_results")[request.node.callspec.id]
    else:
        found = scan_matches_iterated(mesh_shape, mode, width, block, steps,
                                      peel, weights)
    assert found == [True, True]


def test_stencil_with_halo_2d_uses_given_halos(grid_mesh):
    """Pre-exchanged face halos (random, not wrap-around) flow into the right
    cells — including the strip corners, via the corner-free assembly."""
    k = jax.random.PRNGKey(2)
    u = jax.random.normal(k, (18, 14), jnp.float32)
    halos = (
        (jax.random.normal(jax.random.fold_in(k, 1), (1, 14), jnp.float32),
         jax.random.normal(jax.random.fold_in(k, 2), (1, 14), jnp.float32)),
        (jax.random.normal(jax.random.fold_in(k, 3), (18, 1), jnp.float32),
         jax.random.normal(jax.random.fold_in(k, 4), (18, 1), jnp.float32)))
    fn = _star_fn(1)
    got = jax.jit(functools.partial(stencil_with_halo_nd, stencil_fn=fn,
                                    width=1, dims=(0, 1),
                                    subdomains=(2, 3)))(u, halos)
    want = fn(pad_with_halo_nd(u, halos, 1, (0, 1)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_heat2d_2d_mesh_matches_slab_and_numpy(grid_mesh):
    """heat2d_solve on a (rows, cols) topology == the 1-D slab solver == the
    classic numpy 5-point sweep, both schedules."""
    from repro.core.stencil import heat2d_init, heat2d_solve
    from repro.launch.mesh import make_mesh

    u0 = heat2d_init(32, 32)
    mesh1 = make_mesh((1,), ("data",))
    want, res_want = heat2d_solve(u0, mesh1, ("data",), 12, mode="two_phase")
    for mode in ("two_phase", "hdot"):
        got, res = heat2d_solve(u0, grid_mesh, ("rows", "cols"), 12, mode=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(res), np.asarray(res_want),
                                   rtol=1e-5)
    up = np.pad(np.asarray(u0), 1)
    one = 0.25 * (up[:-2, 1:-1] + up[2:, 1:-1] + up[1:-1, :-2] + up[1:-1, 2:])
    got1, _ = heat2d_solve(u0, grid_mesh, ("rows", "cols"), 1, mode="hdot")
    np.testing.assert_allclose(np.asarray(got1), one, rtol=1e-6, atol=1e-7)


def test_hpccg_2d_mesh_matches_slab(grid_mesh):
    """CG on the (y, z) 2-D topology converges identically to the z-slab
    solver — exercises the corner-carrying two-hop exchange."""
    from repro.core.stencil import hpccg_solve
    from repro.launch.mesh import make_mesh

    b = jax.random.normal(jax.random.PRNGKey(3), (10, 12, 12), jnp.float32)
    mesh1 = make_mesh((1,), ("data",))
    _, h_want = hpccg_solve(b, mesh1, ("data",), 15, mode="two_phase")
    for mode in ("two_phase", "hdot"):
        x, h = hpccg_solve(b, grid_mesh, ("rows", "cols"), 15, mode=mode)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_want),
                                   rtol=1e-4)


def test_heat2d_kernel_sharded_matches_plain(grid_mesh):
    """The Pallas tile kernel with the exchanged halo ring, run per-shard on
    a 1x1 grid mesh, equals the plain kernel (both impls)."""
    from repro.kernels.heat2d import ops as heat_ops

    u = jax.random.normal(jax.random.PRNGKey(4), (64, 64), jnp.float32)
    want = heat_ops.heat2d_sweep(u, tile=(32, 32), sweeps=2, impl="ref")
    got = heat_ops.heat2d_sweep_sharded(u, grid_mesh, ("rows", "cols"),
                                        tile=(32, 32), sweeps=2, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    got_p = heat_ops.heat2d_sweep_sharded(u, grid_mesh, ("rows", "cols"),
                                          tile=(32, 32), sweeps=2,
                                          impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_heat2d_kernel_halo_ring_pallas_vs_ref():
    """Random (non-zero) halo ring: pallas strips == ref oracle."""
    from repro.kernels.heat2d import ops as heat_ops

    k = jax.random.PRNGKey(5)
    u = jax.random.normal(k, (64, 96), jnp.float32)
    halo = (jax.random.normal(jax.random.fold_in(k, 1), (1, 96), jnp.float32),
            jax.random.normal(jax.random.fold_in(k, 2), (1, 96), jnp.float32),
            jax.random.normal(jax.random.fold_in(k, 3), (64, 1), jnp.float32),
            jax.random.normal(jax.random.fold_in(k, 4), (64, 1), jnp.float32))
    got = heat_ops.heat2d_sweep(u, tile=(32, 32), sweeps=3, impl="pallas",
                                interpret=True, halo=halo)
    want = heat_ops.heat2d_sweep(u, tile=(32, 32), sweeps=3, impl="ref",
                                 halo=halo)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


if __name__ == "__main__":
    print(json.dumps({c.id: scan_matches_iterated(*c.values)
                      for c in SCAN_CASES if np.prod(c.values[0]) > 1}))
