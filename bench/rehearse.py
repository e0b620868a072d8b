#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell> [...]

For each cell: the input draw, the solve (the timed path) and the float32
reference, at the cell's real size and mesh, each compiled by the TPU's
compiler for chips of a described ``v5e:2x2`` host. Prints each program's
compile seconds and ``memory_analysis`` bytes per chip, and whether the
solve's compiled text holds collectives. Nothing runs, so nothing here is a
time or a result. The persistent compile cache is left off: a described
chip's entries cannot be read back.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import generate  # noqa: E402
import run_cell  # noqa: E402

GIB = 2 ** 30
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, f"{k}_size_in_bytes") / GIB
            for k in ("argument", "output", "temp", "generated_code")}


def rehearse(workload: str, topology: str) -> dict:
    from jax.experimental import topologies

    cell = run_cell.load_cell(workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    mesh = cell.app.make_mesh(cell.mesh, topo.devices[:cell.chips])
    shape = cell.app.global_shape(cell.cfg, mesh)
    sharding = NamedSharding(mesh, P(*mesh.axis_names))
    dtype = jnp.dtype(cell.cfg["dtype"])
    spec = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    programs = {
        "draw": (jax.jit(lambda k: generate.draw(k, shape, dtype, cell.traffic["input"],
                                                  sharding)),
                 jax.eval_shape(lambda: generate.seed_key(0))),
        "solve": (jax.jit(lambda s: cell.app.solve(cell.cfg, mesh, s)), spec),
        "reference": (jax.jit(lambda s: cell.app.reference(cell.cfg, mesh, s)), spec),
    }
    rec = {"workload": workload, "global_shape": list(shape),
           "mesh": list(mesh.devices.shape)}
    for name, (fn, arg) in programs.items():
        t = time.perf_counter()
        compiled = fn.lower(arg).compile()
        rec[name] = {"compile_s": time.perf_counter() - t, "gib": memory(compiled)}
        if name == "solve":
            text = compiled.as_text()
            rec[name]["collectives"] = {c: text.count(f" {c}") for c in COLLECTIVES
                                        if f" {c}" in text}
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    for w in args.workload:
        print(json.dumps(rehearse(w, args.topology)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
