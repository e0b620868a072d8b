"""The benchmark's own tests run on the CPU, on four virtual devices so the
four-chip cell's mesh can be built, with the persistent compile cache in a
directory of their own that is removed at exit.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
from __future__ import annotations

import atexit
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4").strip()
_cache = tempfile.mkdtemp(prefix="bench-test-cache-")
atexit.register(shutil.rmtree, _cache, True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache

BENCH = Path(__file__).resolve().parents[1]
for _p in (str(BENCH.parent / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)
