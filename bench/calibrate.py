#!/usr/bin/env python3
"""Readings that the limits of a cell's correctness numbers are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 ... --control-seeds 1 2 3

In one process on the cell's chips, at its real size: for every seed the
program's reading (the harness's timed path: the input drawn from the
seed, one warm-up solve and, where the traffic carries the output, the
solve after it, compared with the float32 reference), and for the control
seeds the control's (the reference computed in bfloat16, the precision
below the configuration's float32, in the program's place). Prints one JSON
line per seed, then the largest program reading and the smallest control
reading of each number. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import generate  # noqa: E402
import run_cell  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def readings(workload: str, seeds, control_seeds, overrides=None,
             require_tpu: bool = True):
    cell = run_cell.load_cell(workload, overrides)
    devices = run_cell.chips(cell.chips, require_tpu)
    enable_compile_cache()
    app, cfg = cell.app, cell.cfg
    mesh = app.make_mesh(cell.mesh, devices)
    for seed in seeds:
        state = app.make_input(cfg, cell.traffic, mesh, generate.seed_key(seed))
        out = jax.block_until_ready(app.solve(cfg, mesh, state))
        if cell.traffic["carry"] == "output":
            state = app.carry(out)
            out = jax.block_until_ready(app.solve(cfg, mesh, state))
        ref = app.reference(cfg, mesh, state)
        rec = {"seed": seed, "program": app.compare(out, ref)}
        del out
        if seed in control_seeds:
            rec["control"] = app.compare(
                app.reference(cfg, mesh, state, jnp.bfloat16), ref)
        del ref, state
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    worst, best = {}, {}
    for rec in readings(args.workload, args.seeds, set(args.control_seeds)):
        print(json.dumps(rec), flush=True)
        for k, v in rec["program"].items():
            worst[k] = max(worst.get(k, v), v)
        for k, v in rec.get("control", {}).items():
            best[k] = min(best.get(k, v), v)
    print(json.dumps({"workload": args.workload, "program_max": worst,
                      "control_min": best}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
