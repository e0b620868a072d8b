"""The CREAMS app (``bench/apps/creams.py``): its plain reference agrees with
the program's solver at a tiny size on the CPU, its required work equals
its closed form, its input follows the seed, and a whole run of the cell is
correct while the lower-precision control in the program's place is not."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import generate
import run_cell
from apps import creams

CELL = "creams-tgv-256-1chip"
# y and z hold >= 4 halo widths, so the solve takes the stage-carried
# (y, z) path the real size takes
TINY = {"local_grid": [8, 16, 16], "steps": 3}
SEED = 2 ** 31 + 11


def tiny_cell():
    cell = run_cell.load_cell(CELL, TINY)
    return cell, cell.app.make_mesh(cell.mesh, jax.devices()[:cell.chips])


def test_reference_matches_solver():
    """Both compute the same formulas in another order (slices of a padded
    block against rolls, 1/dx multiplied against divided): float32 rounding
    apart. At Mach 0.1 the pressure, about 71, enters every momentum flux,
    so a rounding of the pressure is about 4e-6 of momentum flux, while the
    largest rho w is the 0.01 perturbation: that field reads about 1e-4, a
    tenth of the cell's limit or less."""
    cell, mesh = tiny_cell()
    u0 = cell.app.make_input(cell.cfg, cell.traffic, mesh, generate.seed_key(SEED))
    u, dts = cell.app.solve(cell.cfg, mesh, u0)
    u_ref, dts_ref = cell.app.reference(cell.cfg, mesh, u0)
    assert u.shape == u_ref.shape == u0.shape == (5, 8, 16, 16)
    assert dts.shape == dts_ref.shape == (3,)
    errs = cell.app.compare((u, dts), (u_ref, dts_ref))
    assert errs["state_err"] < 1e-3 and errs["dt_err"] < 1e-6, errs
    assert errs["state_err"] < cell.limits["state_err"] / 10
    assert errs["dt_err"] < cell.limits["dt_err"] / 100


def test_work_closed_form():
    cfg = run_cell.load_cell(CELL).cfg
    cells, steps = 256 ** 3, 20
    assert creams.STAGE == 2639 and 3 * creams.STAGE + creams.CFL == 7944
    assert creams.work(cfg) == {"flops": 7944 * cells * steps,
                                "bytes": 4 * 55 * cells * steps}
    assert creams.work(cfg)["bytes"] == 73819750400
    per_cell = creams.work(dict(cfg, dtype="bfloat16"))["bytes"] / (cells * steps)
    assert per_cell == 2 * 55


def test_input_follows_the_seed():
    cell, mesh = tiny_cell()

    def draw(seed):
        return np.asarray(cell.app.make_input(cell.cfg, cell.traffic, mesh,
                                              generate.seed_key(seed)))

    big = 2 ** 31 + 977
    np.testing.assert_array_equal(draw(big), draw(big))
    assert not np.array_equal(draw(big), draw(big + 1))
    assert not np.array_equal(draw(big), draw(big + 2 ** 32))
    u = draw(big)
    rho, vel = u[0], u[1:4] / u[0]
    p = (cell.cfg["gamma"] - 1) * (u[4] - 0.5 * rho * np.sum(vel ** 2, axis=0))
    mach = np.sqrt(np.sum(vel ** 2, axis=0)) / np.sqrt(cell.cfg["gamma"] * p / rho)
    assert rho.min() > 0.99 and p.min() > 0
    assert 0.09 < mach.max() < 0.11
    assert 0 < np.abs(vel[2]).max() <= 0.01   # w is the perturbation alone


def test_unsupported_physics_is_refused():
    cell, mesh = tiny_cell()
    with pytest.raises(ValueError, match="viscosity"):
        cell.app.solve(dict(cell.cfg, viscosity=1e-3), mesh, None)


def run(solve=None):
    return run_cell.run(CELL, SEED, 0.05, False, require_tpu=False,
                        overrides=TINY, solve=solve)


def test_sound_run_is_correct():
    r = run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["checks"]) == {"state_err", "dt_err"}
    assert set(r["metrics"]) == {"solve_s", "setup_s"}


def test_bfloat16_solve_is_not_correct():
    """The reference in bfloat16 in the program's place: at Mach 0.1 the
    energy is about 180 times the kinetic energy, so the pressure cancels
    in bfloat16."""
    def control(cfg, mesh, s):
        return creams.reference(cfg, mesh, s, jnp.bfloat16)

    r = run(control)
    assert r["correct"] is False and r["failed"] == 1, r["checks"]


def test_calibration_separates_program_from_control():
    limits = run_cell.load_cell(CELL).limits
    recs = list(calibrate.readings(CELL, [5, 6], {5, 6}, overrides=TINY,
                                   require_tpu=False))
    for r in recs:
        assert all(r["program"][k] <= limits[k] for k in limits), r
        assert any(not math.isfinite(r["control"][k]) or r["control"][k] > limits[k]
                   for k in limits), r


@pytest.mark.parametrize("key,value", [("cfl", 0.4), ("gamma", 5 / 3),
                                       ("domain_length", 1.0),
                                       ("weno_order", 3), ("viscosity", 1e-3)])
def test_other_semantics_are_refused(key, value):
    """The program computes one scheme on one box: a configuration asking
    for another is refused, not run as if it were."""
    cell, mesh = tiny_cell()
    u0 = cell.app.make_input(cell.cfg, cell.traffic, mesh, generate.seed_key(5))
    with pytest.raises(ValueError, match=key):
        cell.app.solve(dict(cell.cfg, **{key: value}), mesh, u0)
