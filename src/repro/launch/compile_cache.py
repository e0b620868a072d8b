"""Persistent XLA compilation cache for the entry points.

Called from the launchers' ``main()`` and from ``chip_smoke.py``, never at
import time and never from tests: a process that compiles for a described
(not attached) TPU writes entries it cannot read back, and tests must not
leave files behind.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: a cache directory that moves between runs never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already keeps its cache
    there and no other directory is set; otherwise the cache lives in
    ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
