"""Serving launcher: batched prefill+decode over an architecture config.

``--scheduler continuous`` (default) runs true continuous batching
(token-granular slot re-admission, runtime/server.py:run_continuous);
``--scheduler wave`` runs the static wave baseline. By default the config is
the reduced CPU-sized one; ``--full`` serves the published config (one
accelerator must hold its weights and ``--slots`` x ``--max-len`` KV cache).
"""
from __future__ import annotations

import argparse
from typing import Optional

import jax
import numpy as np


def build_server(arch: str, *, full: bool, slots: int, max_len: int,
                 seed: int = 0):
    """(model, server) for `arch` with seeded random weights: the published
    config with ``full``, the reduced CPU-sized one otherwise."""
    from repro.config.registry import get_arch
    from repro.models.model import ModelOptions, build_model
    from repro.runtime.server import BatchServer

    cfg = get_arch(arch)
    if not full:
        cfg = cfg.reduced()
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(seed))
    return model, BatchServer(model, params, slots=slots, max_len=max_len)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=256,
                    help="per-slot KV cache capacity (prompt + new tokens)")
    ap.add_argument("--full", action="store_true",
                    help="published (non-reduced) config")
    ap.add_argument("--scheduler", choices=("continuous", "wave"),
                    default="continuous",
                    help="continuous = token-granular slot re-admission; "
                         "wave = static batches decoded to the slowest member")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime.server import Request

    enable_compile_cache()
    model, server = build_server(args.arch, full=args.full, slots=args.slots,
                                 max_len=args.max_len)

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(1, model.cfg.vocab_size, args.prompt_len).tolist()
        server.submit(Request(prompt=prompt, max_new_tokens=args.max_new))
    if args.scheduler == "continuous":
        served = server.run_continuous()
    else:
        served = server.run_all()
    for i, r in enumerate(served):
        print(f"[serve] req{i:02d} -> {len(r.output)} tokens: {r.output[:8]}...")
    how = (f"{server.stats['decode_steps']} decode steps"
           if args.scheduler == "continuous"
           else f"{server.stats['waves']} waves")
    print(f"[serve] served {len(served)} requests ({args.scheduler}: {how})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
