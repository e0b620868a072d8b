"""Paper applications on a single device: schedule equivalence (two_phase ==
hdot numerics — the paper's key safety property), convergence, and physics
sanity for Heat2D / CREAMS (compressible Euler RK3) / HPCCG; and the slab
spelling of each solver against its grid spelling."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.stencil import (heat2d_init, heat2d_solve, hpccg_solve,
                                rk3_solve)
from repro.launch.mesh import GRID_AXES, GRID_AXES_3D, make_grid_mesh


@pytest.fixture(scope="module")
def data_mesh():
    from repro.launch.mesh import make_mesh

    return make_mesh((1,), ("data",))


def test_heat2d_schedules_identical(data_mesh):
    u0 = heat2d_init(64, 64)
    u_tp, r_tp = heat2d_solve(u0, data_mesh, ("data",), 20, mode="two_phase")
    u_hd, r_hd = heat2d_solve(u0, data_mesh, ("data",), 20, mode="hdot")
    np.testing.assert_allclose(np.asarray(u_tp), np.asarray(u_hd),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(r_tp), np.asarray(r_hd), rtol=1e-6)


def test_heat2d_residual_decreases(data_mesh):
    u0 = heat2d_init(64, 64)
    _, res = heat2d_solve(u0, data_mesh, ("data",), 50, mode="hdot")
    res = np.asarray(res)
    assert res[-1] < res[0]
    assert (np.diff(res) <= 1e-7).all()  # Jacobi on Laplace is monotone here


def test_heat2d_jacobi_matches_numpy(data_mesh):
    """One sweep equals the classic 5-point numpy update."""
    u0 = heat2d_init(32, 32)
    u1, _ = heat2d_solve(u0, data_mesh, ("data",), 1, mode="hdot")
    up = np.pad(np.asarray(u0), 1)
    want = 0.25 * (up[:-2, 1:-1] + up[2:, 1:-1] + up[1:-1, :-2] + up[1:-1, 2:])
    np.testing.assert_allclose(np.asarray(u1), want, rtol=1e-6, atol=1e-7)


def test_rk3_schedules_identical(data_mesh):
    """The Euler RK3 solver under both schedules: the same state and dt
    history to float32 rounding (the flux tasks are cut differently)."""
    from tests.euler_reference import random_state

    u0 = random_state(jax.random.PRNGKey(0), (12, 12, 32))
    u_tp, dt_tp = rk3_solve(u0, data_mesh, ("data",), 5, mode="two_phase")
    u_hd, dt_hd = rk3_solve(u0, data_mesh, ("data",), 5, mode="hdot")
    np.testing.assert_allclose(np.asarray(u_tp), np.asarray(u_hd),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dt_tp), np.asarray(dt_hd), rtol=1e-6)


def test_rk3_diffusion_smooths(data_mesh):
    """Named for the diffusion stand-in the Euler solver replaced; it now
    checks conservation on z slabs: flux differences telescope on the
    periodic box, so the sums of rho, rho u, rho v, rho w and E change by
    float32 rounding only, while the state itself moves."""
    from tests.euler_reference import random_state

    u0 = random_state(jax.random.PRNGKey(1), (8, 8, 64))
    u, _ = rk3_solve(u0, data_mesh, ("data",), 20, mode="hdot")
    u0n, un = np.asarray(u0, np.float64), np.asarray(u, np.float64)
    drift = np.abs(un.sum(axis=(1, 2, 3)) - u0n.sum(axis=(1, 2, 3)))
    assert np.all(drift <= 1e-7 * np.abs(u0n).sum(axis=(1, 2, 3))), drift
    assert np.max(np.abs(un - u0n)) > 1e-3


def test_hpccg_converges_and_schedules_match(data_mesh):
    b = jax.random.normal(jax.random.PRNGKey(2), (16, 16, 16), jnp.float32)
    x_tp, h_tp = hpccg_solve(b, data_mesh, ("data",), 30, mode="two_phase")
    x_hd, h_hd = hpccg_solve(b, data_mesh, ("data",), 30, mode="hdot")
    np.testing.assert_allclose(np.asarray(h_tp), np.asarray(h_hd), rtol=1e-4)
    h = np.asarray(h_hd)
    assert h[-1] < 1e-3 * h[0]  # CG on the SPD 27-point system converges fast


def test_hpccg_solution_solves_system(data_mesh):
    """A x ~= b for the returned x (the operator on the zero-padded block)."""
    from repro.core.stencil import _sum27

    b = jax.random.normal(jax.random.PRNGKey(3), (12, 12, 12), jnp.float32)
    x, _ = hpccg_solve(b, data_mesh, ("data",), 60, mode="hdot")
    Ax = _sum27(jnp.pad(x, 1))
    rel = float(jnp.linalg.norm(Ax - b) / jnp.linalg.norm(b))
    assert rel < 1e-3


def _slab_and_grid(app, mode):
    """`app` solved on a one-axis mesh and on a grid mesh of size-1 axes:
    the slab spelling and the grid spelling of one decomposition."""
    from tests.euler_reference import random_state

    def mesh(axes):
        return make_grid_mesh(*(1,) * len(axes), axes=axes,
                              devices=jax.devices()[:1])

    if app == "heat2d":
        u0 = heat2d_init(32, 40)
        return [heat2d_solve(u0, mesh(axes), axes, 12, mode=mode)
                for axes in (("data",), GRID_AXES)]
    if app == "hpccg":
        b = jax.random.normal(jax.random.PRNGKey(5), (10, 12, 14), jnp.float32)
        return [hpccg_solve(b, mesh(axes), axes, 20, mode=mode)
                for axes in (("z",), GRID_AXES_3D)]
    u0 = random_state(jax.random.PRNGKey(6), (12, 16, 24))
    return [rk3_solve(u0, mesh(axes), axes, 3, mode=mode)
            for axes in (("z",), GRID_AXES)]


@pytest.mark.parametrize("mode", ["hdot", "two_phase"])
@pytest.mark.parametrize("app", ["heat2d", "hpccg", "creams"])
def test_slab_spelling_matches_grid_spelling(app, mode):
    """A one-axis `mesh_axes` runs the same N-D path as a grid of size-1
    axes: Heat2D bit for bit (grid and residual history); HPCCG solution
    and residual history at the matvec tests' tolerances; CREAMS state and
    dt history at the schedule test's."""
    (slab, slab_hist), (grid, grid_hist) = _slab_and_grid(app, mode)
    slab, grid = np.asarray(slab), np.asarray(grid)
    slab_hist, grid_hist = np.asarray(slab_hist), np.asarray(grid_hist)
    assert slab.shape == grid.shape and slab_hist.shape == grid_hist.shape
    if app == "heat2d":
        np.testing.assert_array_equal(slab, grid)
        np.testing.assert_array_equal(slab_hist, grid_hist)
    elif app == "hpccg":
        np.testing.assert_allclose(slab, grid, rtol=1e-6, atol=1e-5)
        np.testing.assert_allclose(slab_hist, grid_hist, rtol=1e-6, atol=1e-5)
    else:
        np.testing.assert_allclose(slab, grid, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(slab_hist, grid_hist, rtol=1e-6)
