"""Paper Table 4: CREAMS (compressible flow, RK3) hybrid vs pure-MPI gain.

The paper's Sod-tube domain is 20x20x7000 decomposed along z; the hybrid gain
grows from +2.6% (1 node) to +13.3% (16 nodes) because the HDOT schedule
hides the halo exchange behind the per-direction flux tasks.

Here: rk3_solve (compressible Euler, LLF-split WENO5 flux tasks with width-3
halos of the 5-component state, Williamson RK3 with a CFL dt — core/stencil)
on the Taylor-Green vortex, 1..8 virtual devices, both schedules; wall clock
+ per-step collective wire bytes. The x/y flux tasks are the "other tasks"
that hide the z-halo ppermute, exactly Figure 5's dependency graph.
``--mesh RxC`` switches to the 2-D (y, z) grid-mesh decomposition
(stage-carried halos on BOTH axes; the y extent is scaled with the row count
so every shard keeps the pipelined path alive).
"""
from __future__ import annotations

import argparse
from typing import Any, Dict


def worker(devices: int, nz: int, steps: int,
           mesh_shape: str = "") -> Dict[str, Any]:
    import jax
    import numpy as np

    from benchmarks._util import parse_mesh_shape, timeit
    from repro.analysis.hlo import parse_collectives
    from repro.core.stencil import euler_tgv_init, rk3_solve
    from repro.launch.mesh import make_grid_mesh, make_mesh

    if mesh_shape:
        ry, rz = parse_mesh_shape(mesh_shape)  # RK3's grid mesh is (y, z)
        assert ry * rz == devices, (mesh_shape, devices)
        mesh = make_grid_mesh(ry, rz)
        axis = ("rows", "cols")
        # >= 32 y-cells per row shard keeps the pipelined path alive
        shape = (20, 32 * ry, nz)
    else:
        mesh = make_mesh((devices,), ("data",))
        axis = ("data",)
        # paper: 20 x 20 x 7000; scaled-down x/y for CPU wall clock
        shape = (20, 20, nz)
    v0 = euler_tgv_init(shape)
    out: Dict[str, Any] = {"devices": devices, "nz": nz, "steps": steps}
    if mesh_shape:
        out["mesh_shape"] = mesh_shape
    results = {}
    for mode in ("two_phase", "hdot"):
        def solve(v0=v0, mode=mode):
            return rk3_solve(v0, mesh, axis, steps, mode=mode)

        sec = timeit(solve)
        results[mode] = np.asarray(solve()[0])
        lowered = jax.jit(
            lambda v: rk3_solve(v, mesh, axis, 1, mode=mode)).lower(v0)
        coll = parse_collectives(lowered.compile().as_text())
        out[mode] = {"seconds": sec, "steps_per_s": steps / sec,
                     "coll_ops_per_step": len(coll.ops),
                     "coll_wire_bytes_per_step": coll.total_wire_bytes}
    out["numerics_identical"] = bool(
        np.allclose(results["two_phase"], results["hdot"], rtol=2e-5, atol=2e-5))
    out["gain_pct"] = 100.0 * (out["two_phase"]["seconds"]
                               / out["hdot"]["seconds"] - 1.0)
    return out


def run(sizes=(1, 2, 4, 8), nz: int = 1024, steps: int = 10,
        mesh_shapes=()) -> Dict[str, Any]:
    from benchmarks._util import mesh_devices, run_worker

    rows = [run_worker("benchmarks.table4_creams", d,
                       ["--devices", str(d), "--nz", str(nz),
                        "--steps", str(steps)])
            for d in sizes]
    for ms in mesh_shapes:
        d = mesh_devices(ms)
        rows.append(run_worker("benchmarks.table4_creams", d,
                               ["--devices", str(d), "--nz", str(nz),
                                "--steps", str(steps), "--mesh", ms]))
    return {"table": "paper Table 4 (CREAMS RK3)", "rows": rows,
            "paper_gain_pct": {1: 2.58, 2: 3.13, 4: 5.94, 8: 9.97, 16: 13.33}}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--nz", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--mesh", type=str, default="",
                    help="RxC 2-D (y,z) process mesh; empty = z slabs")
    args = ap.parse_args()
    if args.worker:
        from benchmarks._util import emit

        emit(worker(args.devices, args.nz, args.steps, args.mesh))
        return
    rec = run()
    for r in rec["rows"]:
        print(f"devices={r['devices']} mesh={r.get('mesh_shape', '-'):>5s} "
              f"two_phase={r['two_phase']['steps_per_s']:7.2f}/s "
              f"hdot={r['hdot']['steps_per_s']:7.2f}/s gain={r['gain_pct']:+6.2f}% "
              f"identical={r['numerics_identical']}")


if __name__ == "__main__":
    main()
