"""Shared fixtures. Tests run on exactly ONE CPU device — device-count forcing
is reserved for the dry-run and the benchmark subprocess workers."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

# Guard: the in-process suite must see the default single CPU device. CI
# exports XLA_FLAGS=--xla_force_host_platform_device_count=8 at the job level
# (for ad-hoc scripts and the benchmark drivers), so strip the forcing flag
# here — before jax initializes its backend — instead of failing outright.
# The multi-device subprocess workers are unaffected: run_devices() in
# test_system.py and benchmarks/_util.run_worker() overwrite XLA_FLAGS in the
# child environment with their own device counts.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" in _flags:
    os.environ["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", _flags).strip()

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def single_mesh():
    """1-device mesh carrying the production axis names."""
    from repro.launch.mesh import make_single_device_mesh

    return make_single_device_mesh()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def reduced(arch_id: str):
    from repro.config.registry import get_arch

    return get_arch(arch_id).reduced()


@pytest.fixture(scope="module")
def child_results(request):
    """The JSON line the requesting test file prints last when it runs as a
    script on 8 forced host devices: the file's cases that need a real
    multi-device mesh, all computed in one child process."""
    path = Path(request.module.__file__)
    repo = path.parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(repo / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, str(path)], capture_output=True,
                         text=True, timeout=600, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])
