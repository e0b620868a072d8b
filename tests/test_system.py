"""End-to-end system behaviour that requires REAL multi-device execution:
run in subprocess workers with forced host device counts (tests themselves
stay single-device). Marked slow — each worker pays jax re-init."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_devices(code: str, devices: int, timeout: int = 600) -> dict:
    """Run `code` (must print one JSON line last) under `devices` devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_heat2d_4dev_matches_1dev_and_schedules():
    code = """
    import json, jax, numpy as np
    from repro.core.stencil import heat2d_init, heat2d_solve
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    u0 = heat2d_init(64, 64)
    u_tp, r_tp = heat2d_solve(u0, mesh, ("data",), 10, mode="two_phase")
    u_hd, r_hd = heat2d_solve(u0, mesh, ("data",), 10, mode="hdot")
    print(json.dumps({
        "identical": bool(np.allclose(np.asarray(u_tp), np.asarray(u_hd), atol=1e-6)),
        "u_sum": float(np.asarray(u_hd).sum()),
        "residual": float(np.asarray(r_hd)[-1]),
    }))
    """
    multi = run_devices(code, 4)
    single = run_devices(code.replace('make_mesh((4,)', 'make_mesh((1,)'), 1)
    assert multi["identical"] and single["identical"]
    # 4-way decomposition must give the same field as 1 device
    assert multi["u_sum"] == pytest.approx(single["u_sum"], rel=1e-5)
    assert multi["residual"] == pytest.approx(single["residual"], rel=1e-5)


@pytest.mark.slow
def test_collective_matmul_ring_4dev():
    code = """
    import json, functools, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.collective_matmul import ag_matmul, matmul_rs
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("model",))
    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (64, 32), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(k, 1), (32, 64), jnp.float32)
    outs = {}
    for mode in ("two_phase", "hdot"):
        f = jax.jit(jax.shard_map(
            functools.partial(ag_matmul, axis_name="model", mode=mode),
            mesh=mesh, in_specs=(P("model", None), P(None, "model")),
            out_specs=P(None, "model")))
        outs[mode] = np.asarray(f(x, w))
    want = np.asarray(x) @ np.asarray(w)
    h = jax.random.normal(k, (64, 64), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 2), (64, 32), jnp.float32)
    zs = {}
    for mode in ("two_phase", "hdot"):
        f = jax.jit(jax.shard_map(
            functools.partial(matmul_rs, axis_name="model", mode=mode),
            mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=P("model", None)))
        zs[mode] = np.asarray(f(h, v))
    want_z = np.asarray(h) @ np.asarray(v)
    print(json.dumps({
        "ag_ok": bool(np.allclose(outs["hdot"], want, rtol=1e-4, atol=1e-4)),
        "ag_same": bool(np.allclose(outs["hdot"], outs["two_phase"], rtol=1e-5, atol=1e-5)),
        "rs_ok": bool(np.allclose(zs["hdot"], want_z, rtol=1e-4, atol=1e-4)),
        "rs_same": bool(np.allclose(zs["hdot"], zs["two_phase"], rtol=1e-5, atol=1e-5)),
    }))
    """
    r = run_devices(code, 4)
    assert r == {"ag_ok": True, "ag_same": True, "rs_ok": True, "rs_same": True}


@pytest.mark.slow
def test_hierarchical_allreduce_with_compression_8dev():
    """2x4 (pod x data) mesh: staged reduce == plain psum; int8-EF cross-pod
    compression stays within quantization error."""
    code = """
    import json, functools, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.reduction import hierarchical_allreduce
    from repro.optim.compression import make_crosspod_codec
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("pod", "data"))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    # the codec shares one scale across the pod axis (pmax) and divides the
    # psum'd scale back out — psum'ing a naive per-pod scale doubles it
    comp, decomp = make_crosspod_codec("pod")

    def staged(x):
        return hierarchical_allreduce(x, "data", "pod", scatter_dim=0)
    def plain(x):
        return jax.lax.psum(x, ("pod", "data"))
    def compressed(x):
        return hierarchical_allreduce(
            x, "data", "pod", scatter_dim=0,
            compress=comp, decompress=decomp)

    outs = {}
    for name, fn in [("staged", staged), ("plain", plain), ("comp", compressed)]:
        f = jax.jit(jax.shard_map(fn, mesh=mesh,
                                  in_specs=P(("pod", "data")), out_specs=P(("pod", "data"))))
        outs[name] = np.asarray(f(jnp.tile(x, (8, 1))))
    err_staged = float(np.abs(outs["staged"] - outs["plain"]).max())
    rel_comp = float(np.abs(outs["comp"] - outs["plain"]).max()
                     / (np.abs(outs["plain"]).max() + 1e-9))
    print(json.dumps({"err_staged": err_staged, "rel_comp": rel_comp}))
    """
    r = run_devices(code, 8)
    assert r["err_staged"] < 1e-4
    assert r["rel_comp"] < 0.03   # int8 quantization of the cross-pod hop


@pytest.mark.slow
def test_mini_production_cell_lowers_on_16dev():
    """A miniature production mesh (4x4, same axis names) lowers+compiles a
    REDUCED arch through the exact dry-run code path (Cell.lower)."""
    code = """
    import json, dataclasses, jax
    from repro.config.registry import get_arch
    from repro.config.shapes import ShapeConfig
    from repro.config.base import ParallelConfig
    from repro.launch.steps import build_cell
    from repro.launch.mesh import make_mesh
    from repro.models.model import ModelOptions
    from repro.analysis.hlo import parse_collectives

    cfg = get_arch("qwen3-8b").reduced()
    shape = ShapeConfig("mini_train", seq_len=64, global_batch=8, kind="train")
    cell = build_cell(cfg, shape,
                      ModelOptions(attn_impl="dense", scan_layers=True, remat="none"),
                      ParallelConfig(remat="none"))
    mesh = make_mesh((4, 4), ("data", "model"))
    compiled = cell.lower(mesh).compile()
    coll = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    print(json.dumps({
        "ok": True,
        "colls": len(coll.ops),
        "arg_mb": mem.argument_size_in_bytes / 1e6,
    }))
    """
    r = run_devices(code, 16)
    assert r["ok"] and r["colls"] > 0


@pytest.mark.slow
def test_grad_sync_pytree_psum_4dev_mixed_dtypes():
    """Zero-copy bucketed sync == monolithic two-phase sync on a REAL 4-way
    reduction with mixed-dtype leaves (integer-valued: sums are exact)."""
    code = """
    import json, functools, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.overlap import grad_sync
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    k = jax.random.PRNGKey(0)
    tree = {
        "emb": jax.random.randint(k, (16, 8), -4, 5).astype(jnp.bfloat16),
        "w1": jax.random.randint(jax.random.fold_in(k, 1), (33,), -4, 5).astype(jnp.float32),
        "w2": jax.random.randint(jax.random.fold_in(k, 2), (4, 4), -4, 5).astype(jnp.float16),
        "b": jnp.asarray(3.0),
    }
    outs = {}
    for mode in ("two_phase", "hdot"):
        f = jax.jit(jax.shard_map(
            functools.partial(grad_sync, axes="data", mode=mode, num_buckets=3),
            mesh=mesh, in_specs=(P(),), out_specs=P()))
        outs[mode] = f(tree)
    same = all(bool(np.array_equal(np.asarray(outs["hdot"][k], np.float32),
                                   np.asarray(outs["two_phase"][k], np.float32)))
               for k in tree)
    dtypes_kept = all(outs["hdot"][k].dtype == tree[k].dtype for k in tree)
    scaled = bool(np.array_equal(np.asarray(outs["hdot"]["b"]), 4 * 3.0))
    print(json.dumps({"same": same, "dtypes_kept": dtypes_kept, "scaled": scaled}))
    """
    r = run_devices(code, 4)
    assert r == {"same": True, "dtypes_kept": True, "scaled": True}


@pytest.mark.parametrize("devices", [3, 4])
@pytest.mark.slow
def test_matmul_rs_bidirectional_ring(devices):
    """Bidirectional chunked reduce-scatter ring == psum_scatter, on odd AND
    even mesh sizes (odd rings have asymmetric fwd/bwd path lengths)."""
    code = f"""
    import json, functools, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.collective_matmul import matmul_rs
    from repro.launch.mesh import make_mesh
    devices = {devices}
    mesh = make_mesh((devices,), ("model",))
    k = jax.random.PRNGKey(0)
    # s_loc = 15 (odd): bidirectional pieces are UNEVEN, exercising the
    # non-divisor chunk split
    h = jax.random.normal(k, (15 * devices, 8 * devices), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(k, 1), (8 * devices, 16), jnp.float32)
    zs = {{}}
    for mode, chunks in (("two_phase", None), ("hdot", None), ("hdot", 1), ("hdot", 3)):
        f = jax.jit(jax.shard_map(
            functools.partial(matmul_rs, axis_name="model", mode=mode, chunks=chunks),
            mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=P("model", None)))
        zs[f"{{mode}}-{{chunks}}"] = np.asarray(f(h, v))
    want = np.asarray(h) @ np.asarray(v)
    ok = {{name: bool(np.allclose(z, want, rtol=1e-4, atol=1e-4))
          for name, z in zs.items()}}
    print(json.dumps(ok))
    """
    r = run_devices(code, devices)
    assert all(r.values()), r


@pytest.mark.slow
def test_halo_scan_4dev_equals_iterated_apply():
    """Double-buffered halo_scan_nd == iterated stencil_apply_nd across a
    real 4-way ring (periodic and Dirichlet), one mesh axis."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.halo import halo_scan_nd, stencil_apply_nd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    avg3 = lambda p: (p[:-2] + p[1:-1] + p[2:]) / 3.0
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 5), jnp.float32)
    ok = {}
    for periodic in (False, True):
        got, _ = jax.jit(jax.shard_map(
            lambda x: halo_scan_nd(x, avg3, (("data", 0),), 1, 6,
                                   periodic=periodic, subdomains=4),
            mesh=mesh, in_specs=(P("data"),), out_specs=(P("data"), P())))(u)
        def iterate(x):
            for _ in range(6):
                x = stencil_apply_nd(x, avg3, (("data", 0),), 1, periodic,
                                     "hdot", 4)
            return x
        want = jax.jit(jax.shard_map(iterate, mesh=mesh, in_specs=(P("data"),),
                                     out_specs=P("data")))(u)
        ok[str(periodic)] = bool(np.allclose(np.asarray(got), np.asarray(want),
                                             rtol=1e-5, atol=1e-6))
    print(json.dumps(ok))
    """
    r = run_devices(code, 4)
    assert r == {"False": True, "True": True}


@pytest.mark.slow
def test_heat2d_2d_meshes_match_1dev_oracle():
    """2x2 / 4x1 / 1x4 (rows x cols) block decompositions give the SAME field
    and residual history as the 1-device two-phase oracle, both schedules —
    corner correctness included (the corner cells of each shard are computed
    from corner-free face exchanges). Odd shard sizes via a 66x70 grid."""
    code = """
    import json, jax, numpy as np
    from repro.core.stencil import heat2d_init, heat2d_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh
    u0 = heat2d_init(64, 64)
    ref, rres = heat2d_solve(u0, make_mesh((1,), ("data",)), ("data",), 10,
                             mode="two_phase")
    ok = {}
    for rc in ((2, 2), (4, 1), (1, 4)):
        mesh = make_grid_mesh(*rc)
        for mode in ("two_phase", "hdot"):
            u, res = heat2d_solve(u0, mesh, ("rows", "cols"), 10, mode=mode)
            ok[f"{rc[0]}x{rc[1]}-{mode}"] = bool(
                np.allclose(np.asarray(u), np.asarray(ref), rtol=1e-5, atol=1e-6)
                and np.allclose(np.asarray(res), np.asarray(rres), rtol=1e-4))
    u0b = heat2d_init(66, 70)   # odd 33x35 shards on 2x2
    refb, _ = heat2d_solve(u0b, make_mesh((1,), ("data",)), ("data",), 7,
                           mode="two_phase")
    ub, _ = heat2d_solve(u0b, make_grid_mesh(2, 2), ("rows", "cols"), 7,
                         mode="hdot")
    ok["odd"] = bool(np.allclose(np.asarray(ub), np.asarray(refb),
                                 rtol=1e-5, atol=1e-6))
    print(json.dumps(ok))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_hpccg_2d_mesh_matches_1dev_oracle():
    """CG on (y, z) 2-D row blocks: the 27-point corner couplings ride the
    sequential two-hop exchange — convergence identical to 1 device."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.core.stencil import hpccg_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh
    b = jax.random.normal(jax.random.PRNGKey(2), (12, 16, 16), jnp.float32)
    _, href = hpccg_solve(b, make_mesh((1,), ("data",)), ("data",), 20,
                          mode="two_phase")
    ok = {}
    for rc in ((2, 2), (4, 1), (1, 4)):
        for mode in ("two_phase", "hdot"):
            _, h = hpccg_solve(b, make_grid_mesh(*rc), ("rows", "cols"), 20,
                               mode=mode)
            ok[f"{rc[0]}x{rc[1]}-{mode}"] = bool(
                np.allclose(np.asarray(h), np.asarray(href), rtol=1e-3))
    print(json.dumps(ok))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_heat2d_kernel_sharded_2x2_matches_unsharded():
    """Pallas tile kernel under a 2x2 mesh (exchanged halo ring staged as
    block-edge strips) == the unsharded kernel with the same tile grid."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.kernels.heat2d import ops as heat_ops
    from repro.launch.mesh import make_grid_mesh
    u = jax.random.normal(jax.random.PRNGKey(0), (64, 64), jnp.float32)
    want = heat_ops.heat2d_sweep(u, tile=(32, 32), sweeps=3, impl="ref")
    got = heat_ops.heat2d_sweep_sharded(u, make_grid_mesh(2, 2),
                                        ("rows", "cols"), tile=(32, 32),
                                        sweeps=3, impl="ref")
    print(json.dumps({"same": bool(np.allclose(np.asarray(got),
                                               np.asarray(want),
                                               rtol=1e-6, atol=1e-6))}))
    """
    r = run_devices(code, 4)
    assert r == {"same": True}


@pytest.mark.slow
def test_rk3_2d_mesh_matches_1dev_oracle():
    """The Euler RK3 solver on (y, z) grid meshes — stage-carried halos of
    all five components on BOTH axes, the CFL dt a max over the mesh — gives
    the 1-device two-phase oracle's state and dt history, both schedules
    (every shard keeps >= 4 * width cells: the pipelined two-axis path)."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.core.stencil import rk3_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh
    from tests.euler_reference import random_state
    u0 = random_state(jax.random.PRNGKey(0), (8, 48, 48))
    ref, dt_ref = rk3_solve(u0, make_mesh((1,), ("data",)), ("data",), 5,
                            mode="two_phase")
    ok = {}
    for rc in ((2, 2), (4, 1), (1, 4)):
        for mode in ("two_phase", "hdot"):
            got, dt = rk3_solve(u0, make_grid_mesh(*rc), ("rows", "cols"), 5,
                                mode=mode)
            ok[f"{rc[0]}x{rc[1]}-{mode}"] = bool(
                np.allclose(np.asarray(got), np.asarray(ref),
                            rtol=2e-5, atol=2e-5)
                and np.allclose(np.asarray(dt), np.asarray(dt_ref),
                                rtol=1e-6, atol=0))
    print(json.dumps(ok))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_hpccg_3d_mesh_matches_1dev_oracle():
    """CG on HPCCG's native (x, y, z) meshes: ALL the 27-point corner
    couplings — edges and the 8 body corners — ride the chained sequential
    face exchange; convergence identical to 1 device on 2x2x2 and the
    degenerate-axis 4x2x1 / 1x2x4 layouts, with odd per-shard extents
    (12/4=3, 20/4=5, 20/2=10)."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.core.stencil import hpccg_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh
    b = jax.random.normal(jax.random.PRNGKey(2), (12, 20, 20), jnp.float32)
    _, href = hpccg_solve(b, make_mesh((1,), ("data",)), ("data",), 20,
                          mode="two_phase")
    ok = {}
    for parts in ((2, 2, 2), (4, 2, 1), (1, 2, 4)):
        for mode in ("two_phase", "hdot"):
            _, h = hpccg_solve(b, make_grid_mesh(*parts),
                               ("planes", "rows", "cols"), 20, mode=mode)
            ok[f"{'x'.join(map(str, parts))}-{mode}"] = bool(
                np.allclose(np.asarray(h), np.asarray(href), rtol=1e-3))
    print(json.dumps(ok))
    """
    r = run_devices(code, 8)
    assert all(r.values()), r


@pytest.mark.slow
def test_halo_scan_nd_peeled_ppermute_count_8dev():
    """3-D halo_scan_nd: one ppermute pair per axis per step, drain peeled.
    Checked through the HLO schedule linter: the canonical `halo3d` target
    (2x2x2 mesh, steps=2) must lint clean — PAIR-COUNT pins 2 pairs * 3
    axes * 2 steps = 12 collective-permutes and DEAD-DRAIN proves every
    exchange's halos reach compute — while the unpeeled mutation must trip
    DEAD-DRAIN (the drain trip's exchange feeds nothing) and PAIR-COUNT
    (one extra pair per axis)."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    rep = lint_target("halo3d")          # PAIR-COUNT expects 2*3*steps,
    broken = lint_target("broken_unpeeled_halo1d")   # DEAD-DRAIN negative
    print(json.dumps({
        "canonical_ok": rep.ok,
        "permute_count_checked": rep.n_collectives == 12,
        "unpeeled_dead_drain": "DEAD-DRAIN" in {f.rule for f in broken.errors},
        "unpeeled_pair_count": "PAIR-COUNT" in {f.rule for f in broken.errors},
    }))
    """
    r = run_devices(code, 8)
    assert all(r.values()), r


@pytest.mark.slow
def test_solver_ppermute_counts_nd():
    """Compiled-solver collective structure on real meshes, via the HLO
    schedule linter: one exchange pair per decomposed axis per step/stage
    (PAIR-COUNT: hpccg_3d 12 permutes, rk3_2d 24), no dead drain exchange
    (DEAD-DRAIN), and every exchange keeps dataflow-independent interior
    compute to fly behind (NO-OVERLAP-WINDOW). The per-target arithmetic
    lives in lint_targets.PERMUTES_* next to the schedule code."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    out = {}
    for name in ("hpccg_3d", "rk3_2d"):
        rep = lint_target(name)   # PAIR-COUNT pins 12 / 24 permutes,
        out[name] = {"ok": rep.ok,            # DEAD-DRAIN pins no drain
                     "errors": sorted({f.rule for f in rep.errors})}
    print(json.dumps(out))
    """
    r = run_devices(code, 8)
    assert all(v["ok"] for v in r.values()), r


@pytest.mark.slow
def test_fsdp_trainer_4dev_matches_replicated_and_two_phase():
    """The ZeRO-3 oracle: param_shard=True on a real 4-way DP mesh produces
    the SAME losses, params and optimizer moments as the replicated explicit
    hdot step and the two-phase baseline (the same sums, reduce-scattered
    instead of all-reduced; tolerances only absorb f32 summation-order
    freedom in the grad-norm partials), while per-device parameter and
    optimizer residency is EXACTLY 1/4 of the padded flat state — asserted
    by buffer-shape inspection of the committed shards."""
    code = """
    import json, jax, jax.numpy as jnp, numpy as np
    from repro.config.base import ParallelConfig, RunConfig, TrainConfig
    from repro.config.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.runtime.trainer import Trainer

    cfg = get_arch("qwen3-8b").reduced()
    train = TrainConfig(global_batch=8, seq_len=32, warmup_steps=2,
                        total_steps=10, checkpoint_every=10**6,
                        checkpoint_dir="/tmp/repro_fsdp_oracle")
    mesh = make_mesh((4,), ("data",))
    runs = {
        "fsdp": ParallelConfig(param_shard=True, remat="none"),
        "repl": ParallelConfig(param_shard=False, remat="none"),
        "two_phase": ParallelConfig(param_shard=False, overlap="two_phase",
                                    remat="none"),
    }
    state, out = {}, {}
    for name, par in runs.items():
        t = Trainer(RunConfig(cfg, par, train), mesh=mesh)
        t.train(3)
        state[name] = t
    def leaves32(tree):
        return [np.asarray(l, np.float32) for l in jax.tree.leaves(tree)]
    f, r, tp = state["fsdp"], state["repl"], state["two_phase"]
    lf = [m["loss"] for m in f.metrics_log]
    out["losses_equal"] = (
        np.allclose(lf, [m["loss"] for m in r.metrics_log], rtol=1e-6)
        and np.allclose(lf, [m["loss"] for m in tp.metrics_log], rtol=1e-6))
    # vs the replicated hdot step: same per-leaf reduction dtypes, so the
    # only float-order freedom is the grad-norm partial sums (~1e-7 rel)
    out["params_match_repl"] = all(
        np.allclose(a, b, rtol=1e-5, atol=1e-6)
        for a, b in zip(leaves32(f.full_params()), leaves32(r.params)))
    # vs two_phase: its monolithic concat upcasts bf16 grads to f32 before
    # the reduce, so bf16 weights may differ by an ulp after 3 updates
    out["params_match_two_phase"] = all(
        np.allclose(a, c, rtol=1e-2, atol=1e-3)
        for a, c in zip(leaves32(f.full_params()), leaves32(tp.params)))
    # optimizer moments: reassemble the flat f32 shard buffers leaf-wise
    from repro.core.overlap import fsdp_unshard_full
    m_f = fsdp_unshard_full(f.opt_state["m"], f._fsdp_layout)
    out["moments_match"] = all(
        np.allclose(a, b, rtol=1e-5, atol=1e-7)
        for a, b in zip(leaves32(m_f), leaves32(r.opt_state["m"])))
    # residency: each committed shard holds exactly padded/4 elements
    layout = f._fsdp_layout
    def dev_bytes(tree):
        return sum(l.addressable_shards[0].data.size
                   * l.addressable_shards[0].data.dtype.itemsize
                   for l in jax.tree.leaves(tree))
    out["param_shard_bytes_exact"] = dev_bytes(f.params) == layout.shard_bytes()
    full_bytes = sum(
        g.padded * jnp.dtype(g.dtype).itemsize for g in layout.groups)
    out["param_residency_quarter"] = dev_bytes(f.params) * 4 == full_bytes
    mv = {"m": f.opt_state["m"], "v": f.opt_state["v"]}
    full_f32 = sum(g.padded for g in layout.groups) * 4
    out["opt_residency_quarter"] = dev_bytes(mv) * 4 == 2 * full_f32
    print(json.dumps(out))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_fsdp_step_hlo_one_rs_one_ag_per_bucket_reverse_emission():
    """Collective structure of the compiled ZeRO-3 step on 4 devices: exactly
    ONE reduce-scatter and ONE all-gather per flat bucket buffer, each
    scatter output shard-sized (grad residency leaves the program at 1/4),
    all-gathers EMITTED in forward bucket order and reduce-scatters in
    REVERSE — the last-backward bucket's collective enters the program
    first, before every earlier bucket's, which is the priority order XLA's
    latency-hiding scheduler launches them in while the remaining backward
    still computes. Emission order is read off channel_id, which jax assigns
    in trace order (the scheduled text order is backend-dependent)."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    # ONE-RS-ONE-AG pins one shard-sized RS + one full-sized AG per bucket
    # buffer, BUCKET-ORDER pins reverse-topo RS / forward AG emission, and
    # DONATION-LOST pins the donated state aliasing; expectations come from
    # fsdp_layout_for itself (see lint_targets).
    rep = lint_target("lm_fsdp_1d")
    broken = lint_target("broken_double_gather_fsdp")
    print(json.dumps({
        "canonical_ok": rep.ok,
        "double_gather_caught":
            "ONE-RS-ONE-AG" in {f.rule for f in broken.errors},
    }))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_fsdp_streaming_4dev_bit_identical_and_shard_residency():
    """The tentpole contract on a real 4-way DP mesh: streaming ZeRO-3
    (per-layer gather + backward regather) is BIT-identical to the gather-all
    step — losses, params, AdamW moments — while persistent per-device
    parameter residency is exactly layout.shard_bytes() (the gathered
    working set is transient, it never lands in the carried state)."""
    code = """
    import json, tempfile
    import jax, numpy as np
    from repro.config.base import ParallelConfig, RunConfig, TrainConfig
    from repro.config.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models.model import ModelOptions
    from repro.runtime.trainer import Trainer
    cfg = get_arch("qwen3-8b").reduced()
    train = TrainConfig(global_batch=4, seq_len=16, warmup_steps=2,
                        total_steps=8, checkpoint_every=10**6,
                        checkpoint_dir=tempfile.mkdtemp())
    mesh = make_mesh((4,), ("data",))
    # matched options: unfused xent (the streamed loss uses log_softmax) and
    # full remat on both sides, so the two programs are numerically the same
    opts = ModelOptions(attn_impl="dense", scan_layers=False, remat="full",
                        fused_xent=False)
    trainers = {}
    for name, par in {
        "stream": ParallelConfig(param_shard=True, fsdp_streaming=True,
                                 scan_layers=False, remat="full"),
        "gather": ParallelConfig(param_shard=True, scan_layers=False,
                                 remat="full", bucket_order="layer"),
    }.items():
        t = Trainer(RunConfig(cfg, par, train), mesh=mesh, options=opts)
        t.train(2)
        trainers[name] = t
    s, g = trainers["stream"], trainers["gather"]
    out = {
        "losses_bit_equal": [m["loss"] for m in s.metrics_log]
                            == [m["loss"] for m in g.metrics_log],
        "params_bit_equal": all(
            np.array_equal(np.asarray(s.params[k], np.float32),
                           np.asarray(g.params[k], np.float32))
            for k in s.params),
        "moments_bit_equal": all(
            np.array_equal(np.asarray(s.opt_state[mom][k]),
                           np.asarray(g.opt_state[mom][k]))
            for mom in ("m", "v") for k in s.params),
    }
    dev_bytes = sum(l.addressable_shards[0].data.size
                    * l.addressable_shards[0].data.dtype.itemsize
                    for l in jax.tree.leaves(s.params))
    out["param_residency_is_shard"] = (
        dev_bytes == s._fsdp_layout.shard_bytes())
    print(json.dumps(out))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_fsdp_streaming_step_hlo_per_layer_gather_adjacency():
    """Streaming ZeRO-3 lint on 4 devices: the per-layer schedule gathers
    each bucket at its consuming layer (forward order), REGATHERS layer
    buckets inside their remat regions last-backward-first, and keeps at
    most fsdp_working_set gathered buffers live at once — all green with
    zero exposed collectives. The gather-all mutation on the SAME layout
    (its ctx expectations match its own emission) must trip exactly
    AG-ADJACENCY: every gathered weight survives to its backward consumer,
    so all buckets' buffers are live simultaneously."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    rep = lint_target("lm_fsdp_streaming")
    broken = lint_target("broken_gather_all_streaming")
    rules = {f.rule for f in broken.errors}
    print(json.dumps({
        "canonical_ok": rep.ok,
        "gather_all_caught": "AG-ADJACENCY" in rules,
        "gather_all_trips_only_adjacency": rules == {"AG-ADJACENCY"},
    }))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_grad_sync_reverse_topo_emission_order_4dev():
    """The replicated explicit schedule with layer provenance: per-bucket
    psums are EMITTED last-backward-first. channel_id records trace order,
    so the deepest bucket's all-reduce must carry the lowest channel id —
    with order='tree' the same buckets are emitted shallowest-first."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    # BUCKET-ORDER compares channel-id order against make_buckets' own
    # emission sequence ([53, 37, 23, 11] for reverse_topo on the fixture
    # tree); the tree-order mutation must trip exactly that rule.
    rep = lint_target("grad_sync_1d")
    broken = lint_target("broken_tree_grad_sync")
    print(json.dumps({
        "canonical_ok": rep.ok,
        "tree_order_caught":
            "BUCKET-ORDER" in {f.rule for f in broken.errors},
    }))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r


@pytest.mark.slow
def test_halo_scan_peeled_ppermute_count_4dev():
    """The drain-step peel drops one ppermute pair per solve, proven by the
    HLO schedule linter: the canonical 1-D and 2-D halo scans lint clean
    (PAIR-COUNT pins 2*axes*steps permutes, DEAD-DRAIN proves every halo is
    consumed), the unpeeled mutation trips DEAD-DRAIN (the drain exchange's
    result feeds nothing — XLA would reap it only when unrolled; the
    production while-loop lowering executes it) plus PAIR-COUNT, and the
    donation mutation (jit without donate_argnums) trips DONATION-LOST."""
    code = """
    import json
    from repro.analysis.hlo_lint import lint_target
    out = {}
    for name in ("halo1d", "halo2d"):   # PAIR-COUNT pins 2*axes*steps
        rep = lint_target(name)
        out[name + "_ok"] = rep.ok
    broken = lint_target("broken_unpeeled_halo1d")
    rules = {f.rule for f in broken.errors}
    out["unpeeled_dead_drain"] = "DEAD-DRAIN" in rules
    out["unpeeled_extra_pair"] = "PAIR-COUNT" in rules
    nodon = lint_target("broken_no_donate_halo1d")
    out["no_donate_caught"] = (
        "DONATION-LOST" in {f.rule for f in nodon.errors})
    print(json.dumps(out))
    """
    r = run_devices(code, 4)
    assert all(r.values()), r
