"""Each app's plain reference agrees with the program's solver at tiny sizes
on the CPU, and each app's required work equals its closed form."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import generate
import run_cell
from apps import heat2d, hpccg

TINY = {
    "heat2d-16k-1chip": {"local_grid": [48, 64], "sweeps": 7},
    "hpccg-512-1chip": {"local_grid": [12, 16, 20], "max_iter": 6},
    "hpccg-512-1x2x2": {"local_grid": [12, 16, 20], "max_iter": 6},
}
# float32 sums reordered (chunk partials, psums, another association of
# the 27 neighbours) give CG iterates a few ulps apart
CG_RTOL = 1e-5


def solve_and_reference(workload, seed=3):
    cell = run_cell.load_cell(workload, TINY[workload])
    mesh = cell.app.make_mesh(cell.mesh, jax.devices()[:cell.chips])
    state = cell.app.make_input(cell.cfg, cell.traffic, mesh, generate.seed_key(seed))
    out = cell.app.solve(cell.cfg, mesh, state)
    ref = cell.app.reference(cell.cfg, mesh, state)
    return cell, state, out, ref


def test_heat2d_reference_matches_solver_bitwise():
    cell, u0, (u, hist), (u_ref, hist_ref) = solve_and_reference("heat2d-16k-1chip")
    assert u.shape == u0.shape == (48, 64)
    assert hist.shape == hist_ref.shape == (7,)
    np.testing.assert_array_equal(np.asarray(u), np.asarray(u_ref))
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(hist_ref))
    assert cell.app.compare((u, hist), (u_ref, hist_ref)) == {
        "grid_err": 0.0, "resid_err": 0.0}


@pytest.mark.parametrize("workload", ["hpccg-512-1chip", "hpccg-512-1x2x2"])
def test_hpccg_reference_matches_solver(workload):
    cell, b, (x, hist), (x_ref, hist_ref) = solve_and_reference(workload)
    assert x.shape == b.shape == tuple(
        n * m for n, m in zip(TINY[workload]["local_grid"], cell.mesh))
    assert len(x.sharding.device_set) == cell.chips
    np.testing.assert_allclose(np.asarray(hist), np.asarray(hist_ref), rtol=CG_RTOL)
    errs = cell.app.compare((x, hist), (x_ref, hist_ref))
    assert errs["x_err"] < CG_RTOL and errs["resid_err"] < CG_RTOL
    # the residual falls: CG is doing its work, not returning zeros
    assert float(hist[-1]) < 0.5 * float(hist[0])


def test_inputs_follow_the_seed():
    cell = run_cell.load_cell("heat2d-16k-1chip", TINY["heat2d-16k-1chip"])
    mesh = cell.app.make_mesh(cell.mesh, jax.devices()[:1])

    def draw(seed):
        return np.asarray(cell.app.make_input(cell.cfg, cell.traffic, mesh,
                                              generate.seed_key(seed)))

    big = 2 ** 31 + 977
    np.testing.assert_array_equal(draw(big), draw(big))
    assert not np.array_equal(draw(big), draw(big + 1))
    assert not np.array_equal(draw(big), draw(big + 2 ** 32))
    assert 0.0 <= draw(big).min() and draw(big).max() < 1.0


def test_heat2d_work_closed_form():
    cfg = run_cell.load_cell("heat2d-16k-1chip").cfg
    assert heat2d.work(cfg) == {"flops": 7 * 16384 ** 2 * 100,
                                "bytes": 8 * 16384 ** 2 * 100}
    assert heat2d.work(cfg)["bytes"] == 214748364800


def test_hpccg_work_closed_form():
    cfg = run_cell.load_cell("hpccg-512-1chip").cfg
    n = 512 ** 3
    assert hpccg.work(cfg) == {"flops": n * (2 + 37 * 150),
                               "bytes": 4 * n * (2 + 8 * 150)}
    assert hpccg.work(cfg)["bytes"] == 645318836224


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_work_scales_with_itemsize(dtype):
    cfg = dict(run_cell.load_cell("hpccg-512-1chip").cfg, dtype=dtype)
    per_cell = hpccg.work(cfg)["bytes"] / math.prod(cfg["local_grid"])
    assert per_cell == jnp.dtype(dtype).itemsize * (2 + 8 * 150)
