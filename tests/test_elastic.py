"""Elastic re-mesh + straggler reassignment (DESIGN §4's 1000-node posture)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime.ft import reassign_host_shards

REPO = Path(__file__).resolve().parents[1]


@given(n=st.integers(2, 64), k=st.integers(0, 8))
@settings(max_examples=100, deadline=None)
def test_reassignment_covers_all_slices(n, k):
    failed = list(range(0, min(k, n - 1)))
    plan = reassign_host_shards(n, failed)
    served = sorted(s for slices in plan.values() for s in slices)
    assert served == list(range(n))                 # every slice still served
    assert set(plan) == set(range(n)) - set(failed)  # only survivors serve
    loads = [len(v) for v in plan.values()]
    assert max(loads) - min(loads) <= 1              # balanced


def test_reassignment_all_failed_raises():
    with pytest.raises(RuntimeError):
        reassign_host_shards(4, [0, 1, 2, 3])


def test_reassigned_slices_reproduce_global_batch():
    """Survivors materialize the lost host's slice exactly (stateless data)."""
    import numpy as np

    from repro.data.pipeline import SyntheticLMDataset

    ds = SyntheticLMDataset(vocab_size=97, seq_len=8, global_batch=16, seed=1)
    full = ds.batch_at(5)
    plan = reassign_host_shards(4, failed=[2])
    parts = {}
    for host, slices in plan.items():
        for s in slices:
            parts[s] = ds.host_slice(5, s, 4)
    got = np.concatenate([parts[i]["tokens"] for i in range(4)], axis=0)
    np.testing.assert_array_equal(got, full["tokens"])


@pytest.mark.slow
def test_elastic_restore_onto_smaller_mesh():
    """Train on a (2,2) mesh, checkpoint, lose half the devices, restore onto
    (2,1) and keep training — loss trajectory continues finitely and the
    restored params equal the saved ones."""
    code = """
    import json, dataclasses, numpy as np, jax
    from repro.config.base import ParallelConfig, RunConfig, TrainConfig
    from repro.config.registry import get_arch
    from repro.runtime.trainer import Trainer
    from repro.launch.mesh import make_mesh
    import tempfile, os

    d = tempfile.mkdtemp()
    cfg = dataclasses.replace(get_arch("internlm2-1.8b").reduced(), num_layers=2)
    run = RunConfig(model=cfg, parallel=ParallelConfig(remat="none"),
                    train=TrainConfig(global_batch=4, seq_len=32, lr=5e-3,
                                      warmup_steps=1, total_steps=6,
                                      checkpoint_every=2, checkpoint_dir=d))
    mesh_big = make_mesh((2, 2), ("data", "model"))
    t1 = Trainer(run, mesh=mesh_big)
    t1.train(4)
    w_before = float(np.asarray(jax.tree.leaves(t1.params)[0],
                                np.float32).sum())
    del t1

    mesh_small = make_mesh((2, 1), ("data", "model"))   # lost half the chips
    t2 = Trainer(run, mesh=mesh_small)
    assert t2.restore_if_available()
    assert t2.step == 4
    w_after = float(np.asarray(jax.tree.leaves(t2.params)[0],
                               np.float32).sum())
    t2.train(2)
    print(json.dumps({
        "w_match": abs(w_before - w_after) < 1e-3 * (1 + abs(w_before)),
        "final_loss": t2.metrics_log[-1]["loss"],
    }))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["w_match"]
    import numpy as np

    assert np.isfinite(r["final_loss"])
