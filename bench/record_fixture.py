#!/usr/bin/env python3
"""Record the small chip traces that ``bench/tests/test_trace_reduce.py``
reads, through the harness's own traced run at a small size.

    python3 bench/record_fixture.py            # on one TPU chip
    python3 bench/record_fixture.py --chips 4  # on a four-chip host

One chip records ``heat2d-16k-1chip`` at a 1024^2 grid and 4 sweeps a
solve; four chips record ``hpccg-512-1x2x2`` at 32^3 per chip and 4
iterations, whose trace holds collectives. Each is written as
``bench/tests/fixtures/<cell>.xplane.pb``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run_cell  # noqa: E402

SMALL = {
    1: ("heat2d-16k-1chip", {"local_grid": [1024, 1024], "sweeps": 4}),
    4: ("hpccg-512-1x2x2", {"local_grid": [32, 32, 32], "max_iter": 4}),
}
FIXTURES = BENCH / "tests" / "fixtures"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(SMALL), default=1)
    args = ap.parse_args(argv)
    workload, overrides = SMALL[args.chips]
    with tempfile.TemporaryDirectory() as keep:
        result = run_cell.run(workload, 0, 1.0, True, overrides=overrides,
                              keep_trace=keep)
        FIXTURES.mkdir(parents=True, exist_ok=True)
        shutil.copy(Path(keep) / f"{workload}.0.xplane.pb",
                    FIXTURES / f"{workload}.xplane.pb")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
