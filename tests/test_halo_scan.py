"""halo.py schedule machinery: interior chunk tasks, pre-exchanged-halo apply,
and the double-buffered multi-step `halo_scan_nd` driver, on one mesh axis
(the slab spelling ``axes=(("data", 0),)``). All single-device (the
multi-device equivalences live in test_system.py); numerics must be identical
between every schedule/knob setting — the paper's safety property."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.halo import (exchange_halo, halo_scan_nd, stencil_apply_nd,
                             stencil_with_halo_nd)

SLAB = (("data", 0),)


@pytest.fixture(scope="module")
def data_mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((1,), ("data",))


def _avg3(padded: jax.Array) -> jax.Array:
    """width-1 moving average along dim 0 (any trailing dims)."""
    return (padded[:-2] + padded[1:-1] + padded[2:]) / 3.0


def _d2w2(padded: jax.Array) -> jax.Array:
    """width-2 second difference along dim 0 (5-point)."""
    return (padded[:-4] - 0.5 * padded[1:-3] + padded[2:-2]
            - 0.5 * padded[3:-1] + padded[4:])


def _shmap(fn, mesh):
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                                 out_specs=P("data")))


@pytest.mark.parametrize("subdomains", [1, 2, 3, 4, 16])
@pytest.mark.parametrize("periodic", [False, True])
def test_stencil_hdot_subdomains_match_two_phase(data_mesh, subdomains, periodic):
    """The interior chunk knob must not change numerics for any grainsize."""
    u = jax.random.normal(jax.random.PRNGKey(0), (24, 5), jnp.float32)
    want = _shmap(lambda x: stencil_apply_nd(
        x, _avg3, SLAB, 1, periodic, "two_phase"), data_mesh)(u)
    got = _shmap(lambda x: stencil_apply_nd(
        x, _avg3, SLAB, 1, periodic, "hdot", subdomains), data_mesh)(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("mode", ["hdot", "two_phase"])
@pytest.mark.parametrize("width,fn", [(1, _avg3), (2, _d2w2)])
def test_halo_scan_equals_iterated_apply(data_mesh, mode, width, fn):
    """halo_scan_nd(steps=k) == k iterated stencil_apply_nd calls, both
    schedules."""
    steps = 5
    u = jax.random.normal(jax.random.PRNGKey(1), (32, 4), jnp.float32)

    got, _ = jax.jit(jax.shard_map(
        lambda x: halo_scan_nd(x, fn, SLAB, width, steps, periodic=True,
                               mode=mode, subdomains=4),
        mesh=data_mesh, in_specs=(P("data"),),
        out_specs=(P("data"), P())))(u)

    def iterate(x):
        for _ in range(steps):
            x = stencil_apply_nd(x, fn, SLAB, width, True, mode, 4)
        return x

    want = _shmap(iterate, data_mesh)(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_halo_scan_step_outputs(data_mesh):
    """Task partials are reduced per step and stacked per step, in order."""
    u = jnp.ones((16, 3), jnp.float32)
    _, outs = jax.jit(jax.shard_map(
        lambda x: halo_scan_nd(x, _avg3, SLAB, 1, 4, periodic=True,
                               subdomains=4,
                               partial_fn=lambda new, old: jnp.max(
                                   jnp.abs(new - old))),
        mesh=data_mesh, in_specs=(P("data"),),
        out_specs=(P("data"), P())))(u)
    assert outs.shape == (4,)
    np.testing.assert_allclose(np.asarray(outs), 0.0, atol=1e-7)  # constant field


def test_halo_scan_degenerate_block_falls_back(data_mesh):
    """Blocks with no interior (< 4*width rows) still produce identical
    numerics via the two-phase fallback."""
    u = jax.random.normal(jax.random.PRNGKey(2), (6, 3), jnp.float32)  # < 4*2
    got, _ = jax.jit(jax.shard_map(
        lambda x: halo_scan_nd(x, _d2w2, SLAB, 2, 3, periodic=True,
                               subdomains=4),
        mesh=data_mesh, in_specs=(P("data"),),
        out_specs=(P("data"), P())))(u)

    def iterate(x):
        for _ in range(3):
            x = stencil_apply_nd(x, _d2w2, SLAB, 2, True, "two_phase")
        return x

    want = _shmap(iterate, data_mesh)(u)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_stencil_with_halo_uses_given_halos(data_mesh):
    """stencil_with_halo_nd(u, [(lo, hi)]) == two-phase apply on
    concat([lo, u, hi])."""
    u = jax.random.normal(jax.random.PRNGKey(3), (20, 4), jnp.float32)
    lo = jax.random.normal(jax.random.PRNGKey(4), (1, 4), jnp.float32)
    hi = jax.random.normal(jax.random.PRNGKey(5), (1, 4), jnp.float32)
    got = jax.jit(lambda u, lo, hi: stencil_with_halo_nd(
        u, [(lo, hi)], _avg3, width=1, dims=(0,), subdomains=3))(u, lo, hi)
    want = _avg3(jnp.concatenate([lo, u, hi], axis=0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-7)


def test_halo_scan_peel_numerics_identical(data_mesh):
    """Peeling the drain step is schedule-only: bit-identical results and
    per-step outputs vs the unpeeled scan (the ppermute-count drop itself
    needs a real multi-device axis — asserted in test_system.py)."""
    u = jax.random.normal(jax.random.PRNGKey(7), (32, 4), jnp.float32)

    def run(peel):
        return jax.jit(jax.shard_map(
            lambda x: halo_scan_nd(x, _avg3, SLAB, 1, 5, periodic=True,
                                   subdomains=4, peel=peel,
                                   partial_fn=lambda new, old: jnp.max(new)),
            mesh=data_mesh, in_specs=(P("data"),),
            out_specs=(P("data"), P())))(u)

    u_p, outs_p = run(True)
    u_n, outs_n = run(False)
    np.testing.assert_array_equal(np.asarray(u_p), np.asarray(u_n))
    assert outs_p.shape == outs_n.shape == (5,)
    np.testing.assert_array_equal(np.asarray(outs_p), np.asarray(outs_n))


def test_exchange_edges_single_rank(data_mesh):
    """Size-1 axis: periodic wraps own edges, non-periodic returns zeros."""
    u = jnp.arange(12.0).reshape(6, 2)

    def ex(x, periodic):
        return exchange_halo(x, "data", 1, 0, periodic)

    lo_p, hi_p = jax.jit(jax.shard_map(
        functools.partial(ex, periodic=True), mesh=data_mesh,
        in_specs=(P("data"),), out_specs=(P("data"), P("data"))))(u)
    np.testing.assert_array_equal(np.asarray(lo_p), np.asarray(u[-1:]))
    np.testing.assert_array_equal(np.asarray(hi_p), np.asarray(u[:1]))

    lo_z, hi_z = jax.jit(jax.shard_map(
        functools.partial(ex, periodic=False), mesh=data_mesh,
        in_specs=(P("data"),), out_specs=(P("data"), P("data"))))(u)
    np.testing.assert_array_equal(np.asarray(lo_z), 0.0)
    np.testing.assert_array_equal(np.asarray(hi_z), 0.0)
