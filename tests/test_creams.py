"""CREAMS (HDOT paper §4.2) as a compressible Euler solver: ``rk3_solve``
against the plain float32 reference of ``tests/euler_reference.py`` on
seeded random states, at a small size on the CPU, on both topologies and
under both schedules; the dt history; conservation; and the kinetic energy
of the Taylor-Green vortex that ``euler_tgv_init`` builds."""
from __future__ import annotations

import functools

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.stencil import _euler_rhs, euler_tgv_init, rk3_solve
from repro.launch.mesh import make_grid_mesh, make_mesh
from tests import euler_reference as ref

SHAPE = (12, 16, 24)  # y and z >= 4 * width: the pipelined paths run
STEPS = 4
TOPOLOGIES = {"slab": ("data",), "grid": ("rows", "cols")}
MODES = ("hdot", "two_phase")
# The program and the reference compute the same formulas in another order
# (1/dx multiplied where the reference divides, the directions summed in
# another association, the neighbours sliced from a padded block where the
# reference rolls), so they differ by float32 rounding: a few ulps of each
# component's largest value, growing slowly over the steps (readings here
# are below 1e-6). A bfloat16 computation misses by more than 1e-3.
STATE_RTOL = 1e-5
DT_RTOL = 1e-6


def mesh_of(topology):
    return make_mesh((1,), ("data",)) if topology == "slab" else make_grid_mesh(1, 1)


def component_err(got, want):
    """The largest, over the components, of max |got - want| / max |want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return max(np.max(np.abs(got[c] - want[c])) / np.max(np.abs(want[c]))
               for c in range(want.shape[0]))


@pytest.fixture(scope="module")
def u0():
    return ref.random_state(jax.random.PRNGKey(11), SHAPE)


@pytest.fixture(scope="module")
def reference(u0):
    return ref.solve(u0, STEPS)


@functools.lru_cache(maxsize=None)
def _solved(topology, mode):
    u0 = ref.random_state(jax.random.PRNGKey(11), SHAPE)
    return rk3_solve(u0, mesh_of(topology), TOPOLOGIES[topology], STEPS,
                     mode=mode)


@pytest.mark.parametrize("mode", MODES)
def test_rhs_matches_reference(u0, mode):
    """One RHS evaluation: the three flux tasks summed, against the
    reference's -sum_d dF_d/dx_d, on a z slab whose z task goes through the
    halo exchange and (hdot) the face and chunk tasks."""
    mesh = make_mesh((1,), ("data",))
    inv_dx = tuple(1 / d for d in ref.spacing(u0.shape))
    spec = P(None, None, None, "data")
    got = jax.jit(jax.shard_map(
        lambda u: _euler_rhs(u, ("data",), mode, inv_dx),
        mesh=mesh, in_specs=spec, out_specs=spec))(u0)
    want = ref.rhs(u0, ref.spacing(u0.shape))
    assert got.shape == want.shape == (5,) + SHAPE
    assert component_err(got, want) < STATE_RTOL


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("topology", sorted(TOPOLOGIES))
def test_rk3_solve_matches_reference(reference, topology, mode):
    u, dts = _solved(topology, mode)
    u_ref, dts_ref = reference
    assert u.shape == (5,) + SHAPE and dts.shape == (STEPS,)
    assert component_err(u, u_ref) < STATE_RTOL
    np.testing.assert_allclose(np.asarray(dts), np.asarray(dts_ref),
                               rtol=DT_RTOL)


@pytest.mark.parametrize("topology", sorted(TOPOLOGIES))
def test_hdot_matches_two_phase(topology):
    """The schedules differ only in how the flux tasks are cut (faces and
    interior chunks against one padded block): the same formulas on the
    same cells."""
    (u_h, dt_h), (u_t, dt_t) = _solved(topology, "hdot"), _solved(topology, "two_phase")
    assert component_err(u_h, u_t) < STATE_RTOL
    np.testing.assert_allclose(np.asarray(dt_h), np.asarray(dt_t), rtol=DT_RTOL)


def test_topologies_agree():
    """A (y, z) grid mesh's stage-carried halos on both axes give the z
    slab's result."""
    (u_g, dt_g), (u_s, dt_s) = _solved("grid", "hdot"), _solved("slab", "hdot")
    assert component_err(u_g, u_s) < STATE_RTOL
    np.testing.assert_allclose(np.asarray(dt_g), np.asarray(dt_s), rtol=DT_RTOL)


def test_dt_history(u0):
    """Each step's dt is cfl over the largest sum_d (|u_d| + c) / dx_d of
    the state that step starts from: the input's for the first step, and
    the solve's result for the first step of a solve that continues it."""
    u, dts = _solved("grid", "hdot")
    _, dts_next = rk3_solve(u, mesh_of("grid"), TOPOLOGIES["grid"], STEPS)
    dx = ref.spacing(u0.shape)
    for got, state in ((dts[0], u0), (dts_next[0], u)):
        want = float(ref.cfl_dt(state, dx))
        assert abs(float(got) - want) <= DT_RTOL * want
    assert np.all(np.asarray(dts) > 0) and len(set(np.asarray(dts).tolist())) > 1


@pytest.mark.parametrize("mode", MODES)
def test_conservation(mode):
    """Flux differences telescope on a periodic box: the sums of rho,
    rho u, rho v, rho w and E change by float32 rounding only. Readings are
    about 1e-9 of the sum of |U|; one step's true change of any cell is
    about 1e-3 of it."""
    u0 = ref.random_state(jax.random.PRNGKey(12), SHAPE)
    u, _ = rk3_solve(u0, mesh_of("grid"), TOPOLOGIES["grid"], 10, mode=mode)
    u0, u = np.asarray(u0, np.float64), np.asarray(u, np.float64)
    drift = np.abs(u.sum(axis=(1, 2, 3)) - u0.sum(axis=(1, 2, 3)))
    assert np.all(drift <= 1e-7 * np.abs(u0).sum(axis=(1, 2, 3))), drift
    assert component_err(u, u0) > 1e-3  # the state did move


def test_kinetic_energy_does_not_grow():
    """On the inviscid Taylor-Green vortex the kinetic energy is conserved
    by the equations; the scheme's upwinding can only take some away."""
    mesh = mesh_of("grid")
    u = euler_tgv_init((16, 16, 16))
    ke = [float(ref.kinetic_energy(u))]
    for _ in range(5):
        u, _ = rk3_solve(u, mesh, TOPOLOGIES["grid"], 2)
        ke.append(float(ref.kinetic_energy(u)))
    assert np.all(np.diff(ke) <= 0), ke
    assert ke[-1] > 0.99 * ke[0], ke
