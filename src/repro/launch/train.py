"""Training launcher: ``--arch <id>`` + mesh flags -> Trainer loop.

On this CPU container it runs reduced configs end-to-end (the ~100M example
uses it); on a real pod slice the same driver runs the full config — the mesh
flags select make_production_mesh and the step is GSPMD-sharded per
sharding.rules.

Fault tolerance: --restarts N wraps the loop in the FaultTolerantRunner so an
injected/real failure resumes from the latest checkpoint (exact data order).
"""
from __future__ import annotations

import argparse
from typing import Optional

from repro.config.base import ParallelConfig, RunConfig, TrainConfig
from repro.config.registry import get_arch


def build_run(arch: str, *, reduced: bool = True, steps: int = 50,
              global_batch: int = 8, seq_len: int = 128,
              checkpoint_dir: str = "/tmp/repro_ckpt",
              overlap: str = "hdot", accum_steps: int = 1) -> RunConfig:
    cfg = get_arch(arch)
    if reduced:
        cfg = cfg.reduced()
    # namespace per arch: a shared dir would otherwise restore a FOREIGN
    # checkpoint into a mismatched param tree (caught by a KeyError in
    # restore, but the right behavior is isolation)
    checkpoint_dir = f"{checkpoint_dir.rstrip('/')}/{cfg.name}"
    return RunConfig(
        model=cfg,
        parallel=ParallelConfig(overlap=overlap, accum_steps=accum_steps,
                                remat="none" if reduced else "full"),
        train=TrainConfig(global_batch=global_batch, seq_len=seq_len,
                          total_steps=steps, warmup_steps=max(1, steps // 10),
                          checkpoint_every=max(1, steps // 5),
                          checkpoint_dir=checkpoint_dir),
    )


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config — pod-scale hardware only")
    ap.add_argument("--mesh", choices=["none", "single-device", "production",
                                       "production-multipod"], default="none")
    ap.add_argument("--overlap", choices=["hdot", "two_phase"], default="hdot")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--restarts", type=int, default=0,
                    help="fault-tolerant restarts budget (runtime.ft)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import (make_production_mesh,
                                   make_single_device_mesh)
    from repro.runtime.trainer import Trainer

    enable_compile_cache()

    mesh = None
    if args.mesh == "single-device":
        mesh = make_single_device_mesh()
    elif args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh == "production-multipod":
        mesh = make_production_mesh(multi_pod=True)

    run = build_run(args.arch, reduced=not args.full, steps=args.steps,
                    global_batch=args.global_batch, seq_len=args.seq_len,
                    checkpoint_dir=args.checkpoint_dir, overlap=args.overlap,
                    accum_steps=args.accum_steps)
    trainer = Trainer(run, mesh=mesh)

    if args.restarts:
        from repro.runtime.ft import FaultTolerantRunner

        runner = FaultTolerantRunner(lambda: Trainer(run, mesh=mesh),
                                     max_restarts=args.restarts)
        trainer = runner.run(args.steps)
        print(f"[train] reached step {trainer.step} "
              f"({runner.restarts} restarts used)")
    else:
        if args.resume:
            trainer.restore_if_available()
        result = trainer.train(args.steps)
        print(f"[train] {result}")
    losses = [m["loss"] for m in trainer.metrics_log] if trainer.metrics_log else []
    if losses:
        print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
