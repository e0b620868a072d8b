#!/usr/bin/env python3
"""Smoke run of the HDOT system's main paths on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the multi-chip paths of a 4-chip host

One chip runs, in one process:

1. Heat2D (paper §4.1) through ``core.stencil.heat2d_solve`` on a one-chip
   mesh, hdot and two_phase, against a plain ``jax.numpy`` 5-point Jacobi;
2. HPCCG (paper §4.3) through ``hpccg_solve``, hdot against two_phase;
3. the compiled Pallas heat2d tile kernel against its blocked jnp oracle;
4. continuous-batching serving of internlm2-1.8b at its published config
   through ``launch/serve.py``'s server: every request against serving it
   alone, and the prefill logits against the same model in float32.

Four chips run only what exists across chips: Heat2D on 4x1 and 2x2 meshes
and HPCCG on a 1x2x2 mesh, each against its one-device run, and a few
FSDP-streaming training steps of internlm2-1.8b over dp=4 in hdot and
two_phase.

Each phase prints one ``[smoke]`` line: its sizes, the seconds JAX spent
compiling, the wall seconds of a warm smoke run (not a benchmark metric),
its error against the reference and the device's ``peak_bytes_in_use`` so
far. A phase outside its tolerance raises. The last line, printed only when
every phase passed, is ``{"ok": true, "device": {...}}``. Without a TPU the
script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config.base import ParallelConfig, RunConfig, TrainConfig  # noqa: E402
from repro.config.registry import get_arch  # noqa: E402
from repro.core.stencil import heat2d_init, heat2d_solve, hpccg_solve  # noqa: E402
from repro.kernels.heat2d.ops import _ref_blocked, heat2d_sweep  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import (GRID_AXES, GRID_AXES_3D, make_grid_mesh,  # noqa: E402
                               make_mesh)
from repro.launch.serve import build_server  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.runtime.server import Request  # noqa: E402
from repro.runtime.trainer import Trainer  # noqa: E402

# Heat2D: 1 GiB per float32 field; the solver's program needs ~3.2 GiB of
# temporaries on top of its input and output (compiled for v5e).
HEAT_N = 16384
HEAT_SWEEPS = 32
# Every schedule and mesh computes each cell with the reference's float32
# operations in the reference's order; values stay in [0, 1].
HEAT_TOL = 1e-5
# HPCCG takes its grid per process and weak-scales; 256^3 per chip is an
# assumed size (the paper states none). 50 CG iterations keep the residual
# far above float32 round-off, so schedules can be compared on it.
HPCCG_N = 256
HPCCG_ITERS = 50
# hdot, two_phase and other meshes reorder float32 sums (chunked partials,
# psums over more shards); CG carries that drift through 50 iterations.
HPCCG_RTOL = 1e-3
# the residual must fall at least this much over the run
HPCCG_DROP = 0.5
KERNEL_N = 2048
KERNEL_TILE = 256
KERNEL_SWEEPS = 2
KERNEL_TOL = 1e-5        # same float32 adds in the same order as the oracle
SERVE_ARCH = "internlm2-1.8b"
SERVE_SLOTS = 8
SERVE_MAX_LEN = 1024
SERVE_PROMPT_LENS = (128, 256, 512)   # one admission program per length
SERVE_REQUESTS = 12
SERVE_MAX_NEW = 32
# Prefill logits, bf16 serving model against the same weights run in float32
# with "highest" matmul precision: bf16 keeps 8 significant bits, and
# weights, activations and the residual stream round at every op. On the CPU
# at full width that error measured 0.0049 (relative L2) at 1 layer and
# 0.0114 at 8, growing about as the square root of depth: ~0.02 at 24. A
# wrong cache, mask or position gives an error of order one.
SERVE_LOGIT_RTOL = 0.05
TRAIN_STEPS = 3
TRAIN_GLOBAL_BATCH = 8
TRAIN_SEQ_LEN = 1024
# hdot and two_phase must train to the same losses (float32 reductions)
TRAIN_RTOL = 1e-5
SEED = 0


class SmokeFailure(RuntimeError):
    """A phase's result is outside its stated tolerance."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def report(phase: str, record: dict) -> None:
    print(f"[smoke] {phase} {json.dumps(record)}", flush=True)


def peak_bytes(devices) -> int | None:
    """Largest ``peak_bytes_in_use`` over `devices` so far (None where the
    backend keeps no memory statistics)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def warm_seconds(fn, *args) -> float:
    """Wall seconds of one call of an already compiled `fn`."""
    t = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t


def check_spans(tree, mesh, what: str) -> None:
    """Every array in `tree` is laid out over every device of `mesh`."""
    want = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(tree):
        got = leaf.sharding.device_set
        check(got == want, f"{what}: array on {sorted(d.id for d in got)}, "
                           f"mesh spans {sorted(d.id for d in want)}")


# ---------------------------------------------------------------- Heat2D
@functools.partial(jax.jit, static_argnums=1)
def jacobi_reference(u0: jax.Array, sweeps: int) -> jax.Array:
    """Plain 5-point Jacobi with Dirichlet-0 edges, independent of the
    halo machinery: the grid after `sweeps` sweeps."""
    def sweep(_, u):
        p = jnp.pad(u, 1)
        return 0.25 * (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:])

    return jax.lax.fori_loop(0, sweeps, sweep, u0)


def heat2d_phase(n: int, sweeps: int, mesh, reference: jax.Array) -> dict:
    """Heat2D from ``heat2d_init(n, n)`` through ``heat2d_solve`` on the 2-D
    grid `mesh`, hdot and two_phase, each against `reference` (the grid after
    `sweeps` sweeps)."""
    u0 = heat2d_init(n, n)
    rec = {"grid": [n, n], "sweeps": sweeps,
           "mesh": list(mesh.devices.shape), "tol": HEAT_TOL}
    out = {}
    for mode in ("hdot", "two_phase"):
        solve = functools.partial(heat2d_solve, mesh=mesh, mesh_axes=GRID_AXES,
                                  iters=sweeps, mode=mode)
        u, res = jax.block_until_ready(solve(u0))
        check_spans(u, mesh, f"heat2d {mode}")
        rec[f"{mode}_wall_s"] = warm_seconds(solve, u0)
        # a one-device reference lives on one device of a wider mesh
        err = float(jnp.max(jnp.abs(u - jax.device_put(reference, u.sharding))))
        rec[f"{mode}_max_err"] = err
        rec[f"{mode}_residual_last"] = float(res[-1])
        check(err <= HEAT_TOL,
              f"heat2d {mode} on mesh {rec['mesh']}: max error {err} > {HEAT_TOL}")
        out[mode] = u
    rec["hdot_two_phase_bit_identical"] = bool(
        jnp.array_equal(out["hdot"], out["two_phase"]))
    return rec


# ----------------------------------------------------------------- HPCCG
def hpccg_rhs(n: int) -> jax.Array:
    return jax.random.normal(jax.random.PRNGKey(SEED), (n, n, n), jnp.float32)


def hpccg_phase(n: int, iters: int, mesh, reference=None):
    """CG on the 27-point system over an (n, n, n) grid through
    ``hpccg_solve`` on the 3-D `mesh`, hdot against two_phase and, when
    given, both against the `reference` residual history. Returns the record
    and the hdot history."""
    b = hpccg_rhs(n)
    rec = {"grid": [n, n, n], "iters": iters,
           "mesh": list(mesh.devices.shape), "rtol": HPCCG_RTOL}
    hist = {}
    for mode in ("hdot", "two_phase"):
        solve = functools.partial(hpccg_solve, mesh=mesh,
                                  mesh_axes=GRID_AXES_3D, iters=iters, mode=mode)
        x, h = jax.block_until_ready(solve(b))
        check_spans(x, mesh, f"hpccg {mode}")
        rec[f"{mode}_wall_s"] = warm_seconds(solve, b)
        hist[mode] = np.asarray(h, np.float64)
        check(bool(np.all(np.isfinite(hist[mode]))), f"hpccg {mode}: non-finite residual")
        drop = hist[mode][-1] / hist[mode][0]
        rec[f"{mode}_residual_drop"] = drop
        check(drop < HPCCG_DROP,
              f"hpccg {mode}: residual fell only to {drop} of its start")

    def rel(a, b):
        return float(np.max(np.abs(a - b) / np.abs(b)))

    rec["hdot_vs_two_phase_rel"] = rel(hist["hdot"], hist["two_phase"])
    check(rec["hdot_vs_two_phase_rel"] <= HPCCG_RTOL,
          f"hpccg: hdot and two_phase residuals differ by "
          f"{rec['hdot_vs_two_phase_rel']} > {HPCCG_RTOL}")
    if reference is not None:
        for mode in hist:
            r = rel(hist[mode], reference)
            rec[f"{mode}_vs_reference_rel"] = r
            check(r <= HPCCG_RTOL, f"hpccg {mode} on mesh {rec['mesh']}: "
                                   f"residuals off the reference by {r}")
    return rec, hist["hdot"]


# --------------------------------------------------- Pallas heat2d kernel
def kernel_phase(n: int, tile: int, sweeps: int, interpret: bool = False) -> dict:
    """The heat2d tile kernel through ``heat2d_sweep(impl="pallas")`` against
    ``_ref_blocked``. Compiled (``interpret=False``), its program must hold
    the Mosaic kernel, so a silent reference or interpreter path fails."""
    u = jax.random.normal(jax.random.PRNGKey(SEED), (n, n), jnp.float32)
    sweep = jax.jit(functools.partial(
        heat2d_sweep, tile=(tile, tile), sweeps=sweeps, impl="pallas",
        interpret=interpret))
    compiled = sweep.lower(u).compile()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    if not interpret:
        check(has_kernel, "heat2d kernel: compiled program has no tpu_custom_call")
    got = jax.block_until_ready(compiled(u))
    want = jax.jit(_ref_blocked, static_argnums=(1, 2))(u, (tile, tile), sweeps)
    err = float(jnp.max(jnp.abs(got - want)))
    check(err <= KERNEL_TOL, f"heat2d kernel: max error {err} > {KERNEL_TOL}")
    return {"grid": [n, n], "tile": [tile, tile], "sweeps": sweeps,
            "interpret": interpret, "tpu_custom_call": has_kernel,
            "wall_s": warm_seconds(compiled, u), "max_err": err,
            "tol": KERNEL_TOL}


# --------------------------------------------------------------- serving
def serve_phase(arch: str, *, full: bool, slots: int, max_len: int,
                prompt_lens, requests: int, max_new: int) -> dict:
    """Continuous batching through ``launch.serve.build_server``: `requests`
    greedy requests cycling through `prompt_lens`, served together (twice,
    the second warm), then each alone on the same server; outputs must
    match. Then one prompt's prefill logits against the same weights in
    float32 under "highest" matmul precision."""
    model, server = build_server(arch, full=full, slots=slots,
                                 max_len=max_len, seed=SEED)
    cfg = model.cfg
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(1, cfg.vocab_size, prompt_lens[i % len(prompt_lens)]).tolist()
               for i in range(requests)]

    def serve(batch):
        reqs = [Request(prompt=p, max_new_tokens=max_new) for p in batch]
        for r in reqs:
            server.submit(r)
        server.run_continuous()
        return [r.output for r in reqs]

    rec = {"arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
           "heads": [cfg.num_heads, cfg.num_kv_heads], "head_dim": cfg.resolved_head_dim,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "params": cfg.num_params(),
           "slots": slots, "max_len": max_len, "requests": requests,
           "prompt_lens": sorted(set(len(p) for p in prompts)), "max_new": max_new}
    t = time.perf_counter()
    together = serve(prompts)
    rec["first_batch_wall_s"] = time.perf_counter() - t    # compiles included
    t = time.perf_counter()
    again = serve(prompts)
    rec["warm_batch_wall_s"] = time.perf_counter() - t
    check(again == together, "serve: a warm rerun changed the outputs")
    check(all(len(o) == max_new for o in together), "serve: short output")
    alone = [serve([p])[0] for p in prompts]
    diff = [i for i, (a, b) in enumerate(zip(together, alone)) if a != b]
    rec["requests_equal_to_alone"] = requests - len(diff)
    check(not diff, f"serve: requests {diff} differ from serving them alone")

    tokens = jnp.asarray(np.asarray(prompts[0], np.int32)[None])
    params = server.params
    del server                    # frees the slot caches before the f32 copy
    gc.collect()                  # (the server's jitted closures hold it)
    logits = jax.jit(model.prefill)(params, {"tokens": tokens})[0]
    model32 = build_model(cfg, dataclasses.replace(model.opt, dtype=jnp.float32))
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits32 = jax.jit(model32.prefill)(params32, {"tokens": tokens})[0]
    err = float(jnp.linalg.norm(logits - logits32) / jnp.linalg.norm(logits32))
    rec.update(prefill_tokens=len(prompts[0]), prefill_logits_rel_l2=err,
               logit_rtol=SERVE_LOGIT_RTOL,
               prefill_argmax_equal=bool(jnp.argmax(logits) == jnp.argmax(logits32)))
    check(err <= SERVE_LOGIT_RTOL,
          f"serve: bf16 prefill logits off float32 by {err} > {SERVE_LOGIT_RTOL}")
    return rec


# -------------------------------------------------------------- training
def train_phase(arch: str, *, full: bool, layers: int | None, mesh, steps: int,
                global_batch: int, seq_len: int) -> dict:
    """A few FSDP-streaming (ZeRO-3) Trainer steps over the mesh's "data"
    axis, hdot and two_phase; their losses must agree."""
    cfg = get_arch(arch)
    if not full:
        cfg = cfg.reduced()
    if layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    rec = {"arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
           "vocab": cfg.vocab_size, "dp": mesh.size, "steps": steps,
           "global_batch": global_batch, "seq_len": seq_len, "rtol": TRAIN_RTOL}
    losses = {}
    for mode in ("hdot", "two_phase"):
        with tempfile.TemporaryDirectory() as ckpt:
            run = RunConfig(
                model=cfg,
                parallel=ParallelConfig(
                    dp_axes=("data",), overlap=mode, param_shard=True,
                    fsdp_streaming=True, scan_layers=False, remat="full"),
                train=TrainConfig(
                    global_batch=global_batch, seq_len=seq_len,
                    total_steps=steps, warmup_steps=1,
                    checkpoint_every=steps + 1, checkpoint_dir=ckpt, seed=SEED))
            trainer = Trainer(run, mesh=mesh)
            trainer.init_state()
            check_spans((trainer.params, trainer.opt_state["m"]), mesh,
                        f"train {mode} state")
            trainer.train(1)                               # compiles
            t = time.perf_counter()
            trainer.train(steps - 1)
            rec[f"{mode}_wall_s_per_step"] = (time.perf_counter() - t) / (steps - 1)
            losses[mode] = [m["loss"] for m in trainer.metrics_log]
            del trainer           # frees this mode's state before the next's
            gc.collect()
        check(all(math.isfinite(v) for v in losses[mode]), f"train {mode}: non-finite loss")
        rec[f"{mode}_losses"] = losses[mode]
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses["hdot"], losses["two_phase"]))
    rec["hdot_vs_two_phase_rel"] = rel
    check(rel <= TRAIN_RTOL, f"train: hdot and two_phase losses differ by {rel}")
    return rec


# ------------------------------------------------------------------ main
class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from its
    ``/jax/core/compile/`` duration events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs


def run_phase(clock: CompileClock, name: str, fn, *args, **kwargs):
    start = clock.seconds
    t = time.perf_counter()
    out = fn(*args, **kwargs)
    rec = out[0] if isinstance(out, tuple) else out
    rec["compile_s"] = clock.seconds - start
    rec["phase_s"] = time.perf_counter() - t
    rec["peak_bytes_in_use"] = peak_bytes(jax.devices())
    report(name, rec)
    return out


def one_chip(clock: CompileClock) -> None:
    dev = jax.devices()[:1]
    u_ref = jacobi_reference(heat2d_init(HEAT_N, HEAT_N), HEAT_SWEEPS)
    run_phase(clock, "heat2d", heat2d_phase, HEAT_N, HEAT_SWEEPS,
              make_grid_mesh(1, 1, devices=dev), u_ref)
    del u_ref
    run_phase(clock, "hpccg", hpccg_phase, HPCCG_N, HPCCG_ITERS,
              make_grid_mesh(1, 1, 1, devices=dev))
    run_phase(clock, "heat2d_kernel", kernel_phase, KERNEL_N, KERNEL_TILE,
              KERNEL_SWEEPS)
    run_phase(clock, "serve", serve_phase, SERVE_ARCH, full=True,
              slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
              prompt_lens=SERVE_PROMPT_LENS, requests=SERVE_REQUESTS,
              max_new=SERVE_MAX_NEW)


def four_chips(clock: CompileClock) -> None:
    devs = jax.devices()[:4]
    first = devs[:1]
    u_one, _ = heat2d_solve(heat2d_init(HEAT_N, HEAT_N),
                            make_grid_mesh(1, 1, devices=first), GRID_AXES,
                            HEAT_SWEEPS)
    for shape in ((4, 1), (2, 2)):
        run_phase(clock, f"heat2d_{shape[0]}x{shape[1]}", heat2d_phase, HEAT_N,
                  HEAT_SWEEPS, make_grid_mesh(*shape, devices=devs), u_one)
    del u_one
    one_rec, h_one = run_phase(clock, "hpccg_1x1x1", hpccg_phase, HPCCG_N,
                               HPCCG_ITERS, make_grid_mesh(1, 1, 1, devices=first))
    run_phase(clock, "hpccg_1x2x2", hpccg_phase, HPCCG_N, HPCCG_ITERS,
              make_grid_mesh(1, 2, 2, devices=devs), h_one)
    run_phase(clock, "train_fsdp_streaming", train_phase, SERVE_ARCH, full=True,
              layers=None, mesh=make_mesh((4,), ("data",)), steps=TRAIN_STEPS,
              global_batch=TRAIN_GLOBAL_BATCH, seq_len=TRAIN_SEQ_LEN)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the multi-chip "
                         "paths, on four chips of one host")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r} ({devices[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX finds {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"[smoke] {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}; wall seconds are a "
          f"smoke run, not a benchmark", flush=True)
    clock = CompileClock()
    (one_chip if args.chips == 1 else four_chips)(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
