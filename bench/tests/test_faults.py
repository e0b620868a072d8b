"""A whole run of each cell, with the harness's look for a chip skipped, at
a tiny size on the CPU: sound, it is correct; with the timed path broken
underneath, or with the lower-precision control in the program's place,
``correct`` comes out false."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import repro.core.halo as halo
import repro.core.stencil as stencil
import calibrate
import run_cell
from apps import heat2d

TINY = {
    "heat2d-16k-1chip": {"local_grid": [64, 64], "sweeps": 100},
    "hpccg-512-1chip": {"local_grid": [16, 16, 16], "max_iter": 10},
    "hpccg-512-1x2x2": {"local_grid": [16, 16, 16], "max_iter": 10},
}
CELLS = sorted(TINY)
SEED = 2 ** 31 + 11


def run(workload, solve=None, trace=False):
    return run_cell.run(workload, SEED, 0.05, trace, require_tpu=False,
                        overrides=TINY[workload], solve=solve)


def app_of(workload):
    return run_cell.load_cell(workload).app


def unchanged(app):
    """The solve returns its state unchanged: the input grid, or x = 0."""
    def solve(cfg, mesh, s):
        _, hist = app.solve(cfg, mesh, s)
        return (s if app is heat2d else jnp.zeros_like(s)), hist
    return solve


def half_left_out(app):
    """Half of the domain is left out: its rows keep their input (Heat2D)
    or the solve sees a right-hand side zero there (HPCCG)."""
    def solve(cfg, mesh, s):
        half = jnp.arange(s.shape[0])[:, None] < s.shape[0] // 2
        half = half.reshape(half.shape + (1,) * (s.ndim - 2))
        if app is heat2d:
            out, hist = app.solve(cfg, mesh, s)
            return jnp.where(half, out, s), hist
        return app.solve(cfg, mesh, jnp.where(half, s, 0.0))
    return solve


def altered(app):
    """One answer altered where it is produced: one cell moved by 1% of
    the largest."""
    def solve(cfg, mesh, s):
        out, hist = app.solve(cfg, mesh, s)
        idx = tuple(n // 3 for n in out.shape)
        return out.at[idx].add(0.01 * jnp.max(jnp.abs(out))), hist
    return solve


def control(app):
    """The reference in bfloat16, the precision below the configuration's
    float32, in the program's place."""
    def solve(cfg, mesh, s):
        return app.reference(cfg, mesh, s, jnp.bfloat16)
    return solve


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(run_cell.load_cell(workload).limits)


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered, control])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault):
    r = run(workload, solve=fault(app_of(workload)))
    assert r["correct"] is False and r["failed"] == 1, r["checks"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    """The four-chip cell with every halo exchange between chips replaced
    by zeros, as if each chip had no neighbour."""
    def no_exchange(lo_edge, hi_edge, axis_name, periodic=False):
        return jnp.zeros_like(hi_edge), jnp.zeros_like(lo_edge)

    stencil._hpccg_solver.cache_clear()
    monkeypatch.setattr(halo, "exchange_edges", no_exchange)
    try:
        r = run("hpccg-512-1x2x2")
    finally:
        stencil._hpccg_solver.cache_clear()
    assert r["correct"] is False, r["checks"]


def test_no_chip_refused():
    with pytest.raises(run_cell.NoChip):
        run_cell.run("heat2d-16k-1chip", 0, 0.05, False,
                     overrides=TINY["heat2d-16k-1chip"])
    with pytest.raises(run_cell.NoChip):
        run_cell.chips(len(jax.devices()) + 1, require_tpu=False)


@pytest.mark.parametrize("workload", CELLS)
def test_calibration_separates_program_from_control(workload):
    """The readings the limits are set from, at a tiny size: the program's
    under each limit, the control's over at least one."""
    limits = run_cell.load_cell(workload).limits
    recs = list(calibrate.readings(workload, [5, 6], {5, 6}, overrides=TINY[workload],
                                   require_tpu=False))
    assert [r["seed"] for r in recs] == [5, 6]
    for r in recs:
        assert all(r["program"][k] <= limits[k] for k in limits), r
        assert any(r["control"][k] > limits[k] for k in limits), r
