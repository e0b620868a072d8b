import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first backend init). Everything below is ordinary code.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production meshes (16x16 single-pod, 2x16x16 multi-pod) and extract the
roofline terms from the compiled artifact.

Per cell:
  runnable pass  — scan-over-layers lowering (the production step). Proves
                   compile + sharding coherence; memory_analysis() is the
                   HBM-fit proof.
  analysis pass  — layers-unrolled lowering at k0 and k1 = k0 + period layers;
                   FLOPs / bytes / collective-wire-bytes extrapolate linearly
                   to the full depth (exact for uniform stacks; XLA counts
                   scan bodies ONCE, measured in the pre-build probe, so the
                   scanned module *cannot* provide per-step FLOPs).

Usage:
  PYTHONPATH=src python src/repro/launch/dryrun.py --arch qwen3-8b --shape train_4k
  PYTHONPATH=src python src/repro/launch/dryrun.py --all            # every cell
  PYTHONPATH=src python src/repro/launch/dryrun.py --report         # aggregate
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

DEFAULT_OUT = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# Per-arch overrides applied to BOTH passes (recorded in the JSON).
#  - llama3-405b: fp32 AdamW moments alone exceed v5e-256 HBM (405B*8B/256 =
#    12.7 GB/chip); bf16 moments are the documented production choice here.
#    accum_steps=8 bounds remat residual saves + logits to one microbatch
#    (EXPERIMENTS §Dry-run: 106 GB/chip temp without, fits multi-pod with).
ARCH_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "llama3-405b": {"moment_dtype": "bfloat16", "accum_steps": 8},
}


def _build(arch: str, shape_name: str, analysis: bool, num_layers: Optional[int]):
    import jax.numpy as jnp

    from repro.config.registry import get_arch
    from repro.config.shapes import shape_by_name
    from repro.config.base import ParallelConfig
    from repro.launch.steps import build_cell
    from repro.models.model import ModelOptions

    cfg = get_arch(arch)
    shape = shape_by_name(shape_name)
    if num_layers is not None:
        kw = {"num_layers": num_layers}
        cfg = dataclasses.replace(cfg, **kw)
    over = ARCH_OVERRIDES.get(arch, {})
    moment_dtype = jnp.dtype(over.get("moment_dtype", "float32"))

    # Blockwise attention everywhere seq is long enough to matter: the dense
    # path materializes (b, s, s) f32 score tensors that blow the per-chip
    # temp budget at 4k+ (measured: 39.7 GB/chip for internlm2 train_4k dense
    # vs blockwise — see EXPERIMENTS.md §Dry-run). Decode always uses the
    # ring-cache dense path (one query token).
    if analysis:
        # accum kept at 1: FLOPs/collectives per token are accum-invariant and
        # the k0/k1 unrolled extrapolation must not nest a microbatch scan.
        options = ModelOptions(
            attn_impl="blockwise_unrolled" if shape.kind != "decode" else "dense",
            scan_layers=False,
            remat="full" if shape.kind == "train" else "none",
            unroll_chunks=True)
        parallel = ParallelConfig(scan_layers=False, remat=options.remat)
    else:
        options = ModelOptions(
            attn_impl="blockwise" if shape.kind != "decode" else "dense",
            scan_layers=True,
            remat="full" if shape.kind == "train" else "none")
        parallel = ParallelConfig(scan_layers=True, remat=options.remat,
                                  accum_steps=int(over.get("accum_steps", 1)))
    return build_cell(cfg, shape, options, parallel, moment_dtype)


def _layer_period(arch: str) -> int:
    from repro.config.registry import get_arch

    cfg = get_arch(arch)
    if cfg.family == "hybrid":
        return len(cfg.hybrid.pattern)
    return 1


def _extract(compiled, lowered_text: Optional[str] = None) -> Dict[str, Any]:
    from repro.analysis.hlo import count_ops, parse_collectives

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    coll = parse_collectives(text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "coll_wire_bytes": coll.total_wire_bytes,
        "coll_wire_bytes_bf16eq": coll.total_wire_bytes_bf16eq,
        "coll_operand_bytes": coll.total_operand_bytes,
        "coll_by_kind": {k: [n, b] for k, (n, b) in coll.by_kind().items()},
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "op_counts": {op: count_ops(text, op)
                      for op in ("fusion", "while", "dot", "custom-call",
                                 "transpose", "reshape")},
    }


def _analytic_traffic(cell, cfg, shape, mesh) -> Dict[str, float]:
    """Analytic per-chip HBM traffic (DESIGN §6; memtraffic module)."""
    from repro.analysis.memtraffic import hbm_traffic, sharded_bytes

    ctx = cell.context(mesh)
    chips = mesh.devices.size
    pb = sharded_bytes(cell.arg_specs[0], cell.arg_axes[0], ctx)
    mb = cb = 0.0
    if cell.kind == "train":
        mb = sharded_bytes(cell.arg_specs[1]["m"], cell.arg_axes[1]["m"], ctx) * 2
    elif cell.kind == "decode":
        cb = sharded_bytes(cell.arg_specs[1], cell.arg_axes[1], ctx)
    traffic = hbm_traffic(cfg, shape, chips, pb, mb, cb,
                          remat=(cell.kind == "train"))
    return {"param_bytes_chip": pb, "moment_bytes_chip": mb,
            "cache_bytes_chip": cb, "hbm_traffic_chip": traffic}


def run_cell(arch: str, shape_name: str, multi_pod: bool, analysis: bool,
             out_dir: Path) -> Dict[str, Any]:
    """Lower+compile one cell on one mesh; write JSON; return the record."""
    import jax

    from repro.config.registry import get_arch
    from repro.config.shapes import cell_is_runnable, shape_by_name
    from repro.launch.mesh import make_production_mesh, validate_production_mesh

    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}" + ("__analysis" if analysis else "")
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "analysis": analysis, "tag": tag,
        "jax_devices": len(jax.devices()),
    }
    out_dir.mkdir(parents=True, exist_ok=True)

    cfg = get_arch(arch)
    shape = shape_by_name(shape_name)
    if not cell_is_runnable(cfg.subquadratic, shape):
        rec.update(skipped=True,
                   reason="long_500k requires sub-quadratic attention; "
                          f"{arch} is pure full-attention (DESIGN.md §5)")
        (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
        print(f"[dryrun] SKIP {tag}: {rec['reason']}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    validate_production_mesh(mesh, multi_pod=multi_pod)
    try:
        if analysis:
            period = _layer_period(arch)
            k0, k1 = period, 2 * period
            metrics = {}
            for k in (k0, k1):
                cell = _build(arch, shape_name, analysis=True, num_layers=k)
                t0 = time.time()
                lowered = cell.lower(mesh)
                compiled = lowered.compile()
                m = _extract(compiled)
                m["lower_compile_s"] = time.time() - t0
                metrics[k] = m
            L = cfg.num_layers
            extrap: Dict[str, Any] = {}
            for key in ("flops", "bytes_accessed", "coll_wire_bytes",
                        "coll_wire_bytes_bf16eq", "coll_operand_bytes"):
                per = (metrics[k1][key] - metrics[k0][key]) / (k1 - k0)
                extrap[key] = metrics[k1][key] + per * (L - k1)
                extrap[f"{key}_per_layer"] = per
            rec.update(ok=True, k0=k0, k1=k1, layers=L,
                       raw={str(k): metrics[k] for k in metrics},
                       extrapolated=extrap)
        else:
            cell = _build(arch, shape_name, analysis=False, num_layers=None)
            t0 = time.time()
            lowered = cell.lower(mesh)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            rec.update(ok=True, lower_s=t_lower, compile_s=t_compile,
                       **_extract(compiled))
            rec["analytic"] = _analytic_traffic(cell, cfg, shape, mesh)
            print(compiled.memory_analysis())
    except Exception as e:  # recorded, not raised: the report shows red cells
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    status = "OK" if rec.get("ok") else "FAIL"
    print(f"[dryrun] {status} {tag}")
    return rec


# --------------------------------------------------------------------- report
def load_records(out_dir: Path) -> List[Dict[str, Any]]:
    return [json.loads(p.read_text()) for p in sorted(out_dir.glob("*.json"))]


def report(out_dir: Path) -> str:
    from repro.analysis.roofline import RooflineReport, model_flops_for
    from repro.config.registry import get_arch
    from repro.config.shapes import shape_by_name

    recs = load_records(out_dir)
    runnable = [r for r in recs if not r.get("analysis")]
    analysis = {(r["arch"], r["shape"]): r for r in recs
                if r.get("analysis") and r.get("ok")}

    lines = ["## Dry-run results", "",
             "| arch | shape | mesh | status | compile s | args GB/chip | temp GB/chip |",
             "|---|---|---|---|---|---|---|"]
    for r in sorted(runnable, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if r.get("skipped"):
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"SKIP ({r['reason'][:40]}...) | – | – | – |")
            continue
        if not r.get("ok"):
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"**FAIL** {r.get('error', '')[:60]} | – | – | – |")
            continue
        mem = r["mem"]
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | ok | "
            f"{r.get('compile_s', 0):.1f} | {mem['argument_bytes']/1e9:.2f} | "
            f"{mem['temp_bytes']/1e9:.2f} |")

    runnable_by_key = {(r["arch"], r["shape"]): r for r in runnable
                       if r.get("ok") and r["mesh"] == "16x16"}
    baseline_dir = out_dir.parent / "dryrun_baseline"
    baselines = {}
    if baseline_dir.exists():
        for rec in (json.loads(p.read_text())
                    for p in baseline_dir.glob("*__analysis.json")):
            if rec.get("ok"):
                baselines[(rec["arch"], rec["shape"])] = rec

    lines += ["", "## Roofline (single-pod 16x16; FLOPs/collectives from the "
              "unrolled analysis lowering, t_mem from the analytic HBM model; "
              "t_coll* = bf16-equivalent wire, see analysis/hlo.py)",
              "",
              "| arch | shape | t_comp ms | t_mem ms | t_coll* ms | dominant | "
              "useful ratio | roofline frac | coll GB vs baseline |",
              "|---|---|---|---|---|---|---|---|---|"]
    for (arch, shape_name), r in sorted(analysis.items()):
        if r["mesh"] != "16x16":
            continue
        cfg = get_arch(arch)
        shape = shape_by_name(shape_name)
        tokens = (shape.global_batch if shape.kind == "decode"
                  else shape.global_batch * shape.seq_len)
        mf = model_flops_for(cfg.active_params(), tokens, shape.kind)
        e = r["extrapolated"]
        coll = e.get("coll_wire_bytes_bf16eq", e["coll_wire_bytes"])
        run = runnable_by_key.get((arch, shape_name), {})
        hbm = run.get("analytic", {}).get("hbm_traffic_chip",
                                          e["bytes_accessed"])
        rep = RooflineReport(
            arch=arch, shape=shape_name, mesh=r["mesh"], chips=256,
            hlo_flops=e["flops"], hlo_bytes=hbm,
            coll_bytes=coll, model_flops=mf)
        base = baselines.get((arch, shape_name))
        if base:
            b_coll = base["extrapolated"]["coll_wire_bytes"]
            delta = (f"{b_coll/1e9:.1f} → {e['coll_wire_bytes']/1e9:.1f} "
                     f"({b_coll/max(e['coll_wire_bytes'], 1e-9):.1f}x)")
        else:
            delta = "–"
        lines.append(
            f"| {arch} | {shape_name} | {rep.t_comp*1e3:.2f} | "
            f"{rep.t_mem*1e3:.2f} | {rep.t_coll*1e3:.2f} | {rep.dominant} | "
            f"{rep.useful_flops_ratio:.3f} | {rep.roofline_fraction:.3f} | "
            f"{delta} |")
    return "\n".join(lines)


# ----------------------------------------------------------------------- main
def all_cells() -> List[Dict[str, Any]]:
    from repro.config.registry import list_archs
    from repro.config.shapes import SHAPES

    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            cells.append({"arch": arch, "shape": shape})
    return cells


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--analysis", action="store_true",
                    help="unrolled analysis pass (single-pod roofline terms)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT)
    args = ap.parse_args(argv)

    if args.report:
        print(report(args.out))
        return 0

    todo = (all_cells() if args.all
            else [{"arch": args.arch, "shape": args.shape}])
    rc = 0
    for cell in todo:
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]
        for multi in meshes:
            if args.analysis and multi:
                continue  # roofline table is single-pod only (brief)
            r = run_cell(cell["arch"], cell["shape"], multi_pod=multi,
                         analysis=args.analysis, out_dir=args.out)
            if not (r.get("ok") or r.get("skipped")):
                rc = 1
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
