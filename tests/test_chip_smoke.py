"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas kernels in
interpret mode), each phase's refusal of a wrong result, and the script's
refusal to run without a TPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke as smoke
from repro.core.stencil import heat2d_init
from repro.launch.mesh import make_grid_mesh, make_mesh


@pytest.fixture(scope="module")
def first_device():
    return jax.devices()[:1]


def test_heat2d_phase_against_jacobi(first_device):
    n, sweeps = 64, 6
    ref = smoke.jacobi_reference(heat2d_init(n, n), sweeps)
    rec = smoke.heat2d_phase(n, sweeps, make_grid_mesh(1, 1, devices=first_device), ref)
    assert rec["hdot_max_err"] <= smoke.HEAT_TOL
    assert rec["two_phase_max_err"] <= smoke.HEAT_TOL
    assert rec["hdot_two_phase_bit_identical"]
    with pytest.raises(smoke.SmokeFailure, match="max error"):
        smoke.heat2d_phase(n, sweeps, make_grid_mesh(1, 1, devices=first_device),
                           jnp.zeros_like(ref))


def test_hpccg_phase_schedules_and_reference(first_device):
    mesh = make_grid_mesh(1, 1, 1, devices=first_device)
    rec, hist = smoke.hpccg_phase(16, 20, mesh)
    assert rec["hdot_vs_two_phase_rel"] <= smoke.HPCCG_RTOL
    assert rec["hdot_residual_drop"] < smoke.HPCCG_DROP
    rec, _ = smoke.hpccg_phase(16, 20, mesh, reference=hist)
    assert rec["two_phase_vs_reference_rel"] <= smoke.HPCCG_RTOL
    with pytest.raises(smoke.SmokeFailure, match="off the reference"):
        smoke.hpccg_phase(16, 20, mesh, reference=2 * hist)


def test_kernel_phase_interpret():
    rec = smoke.kernel_phase(128, 64, 2, interpret=True)
    assert rec["max_err"] <= smoke.KERNEL_TOL
    assert not rec["tpu_custom_call"]


def test_serve_phase_reduced():
    rec = smoke.serve_phase("internlm2-1.8b", full=False, slots=2, max_len=32,
                            prompt_lens=(4, 7), requests=3, max_new=4)
    assert rec["requests_equal_to_alone"] == 3
    assert rec["prompt_lens"] == [4, 7]
    assert rec["prefill_logits_rel_l2"] <= smoke.SERVE_LOGIT_RTOL


def test_train_phase_reduced():
    rec = smoke.train_phase("internlm2-1.8b", full=False, layers=2,
                            mesh=make_mesh((1,), ("data",)), steps=2,
                            global_batch=2, seq_len=16)
    assert rec["hdot_losses"] == rec["two_phase_losses"]
    assert all(np.isfinite(rec["hdot_losses"]))


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "no TPU" in out.err
