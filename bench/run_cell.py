#!/usr/bin/env python3
"""One run of one benchmark cell on TPU chips.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything about
it is found by name: its configuration in ``bench/configs/<config>.json``,
which names the app adapter ``bench/apps/<app>.py`` (inputs, the program's
solver, the plain reference, the required work); its traffic in
``bench/traffic/<traffic>.json`` (input distribution, carry; see
``generate.py``); its mesh and the limits of its correctness numbers in
``bench/workloads/<cell>.json``; and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

A run, in order: turn on the persistent compile cache; refuse any device
that is not a TPU, and fewer chips than the cell asks for; draw the input
on the device from ``--seed``; warm up the cell's solve program (set-up
ends here); run whole solves back to back, each ended by
``block_until_ready`` and the next dispatched before the host waits, until
``--seconds`` have passed (``--trace 1`` instead records
``TRACE_SOLVES`` solves under the profiler); read the peak device memory; compare the last solve with the plain reference; print the
numbers compared beside their limits on standard error and one JSON line on
standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

T_IMPORTED = time.perf_counter()

import generate  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# solves the --trace 1 run records: two or more, since the loop keeps one
# solve queued behind the one running
TRACE_SOLVES = 3


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A cell's entry and everything its names lead to."""
    name: str
    chips: int
    mesh: tuple
    cfg: dict
    traffic: dict
    limits: dict
    app: object
    end_to_end: list
    per_layer: list


def load_cell(name: str, overrides: dict | None = None) -> Cell:
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = load_json(BENCH / "configs" / f"{entry['config']}.json")
    cfg.update(overrides or {})
    traffic = generate.validate(load_json(BENCH / "traffic" / f"{entry['traffic']}.json"))
    layout = load_json(BENCH / "workloads" / f"{name}.json")
    mesh = tuple(layout["mesh"])
    if math.prod(mesh) != entry["chips"]:
        raise ValueError(f"{name}: mesh {mesh} does not span {entry['chips']} chips")

    def reported(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reported(m) and m["moves"] in moved]
    return Cell(name, entry["chips"], mesh, cfg, traffic, layout["limits"],
                importlib.import_module(f"apps.{cfg['app']}"),
                end_to_end, per_layer)


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chips(n: int, require_tpu: bool):
    """The first `n` devices; with `require_tpu`, TPU chips whose peaks are
    known, or an error."""
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r} "
                         f"({devices[0].device_kind})")
        peaks.peaks(devices[0].device_kind)
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")
    return devices[:n]


class CompileCounter:
    """Counts JAX's tracing, lowering and compiling events."""

    def __init__(self):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.events += 1


def peak_bytes(devices) -> int | None:
    """Largest ``peak_bytes_in_use`` over `devices` (None where the backend
    keeps no memory statistics)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def solve_loop(step, state, carry, enough, span=None):
    """Whole solves back to back, each ended by ``block_until_ready``. The
    next solve is dispatched before the host waits on the current one, so a
    host stall shorter than a solve leaves the chip busy. After each solve,
    ``enough(solves, seconds)`` says whether to stop dispatching; the solve
    in flight then still ends and counts. Returns the last solve's input and
    output, the solves, the seconds up to the end of the last, and each
    solve's wait. Only the solves in flight hold their inputs and outputs.
    `span(name)`, where given, marks each dispatch and wait on the host."""
    span = span or (lambda name: contextlib.nullcontext())
    waits = []
    t0 = time.perf_counter()
    with span("dispatch"):
        out = step(state)
    more = True
    while True:
        if more:
            nxt_state = carry(state, out)
            with span("dispatch"):
                nxt_out = step(nxt_state)
        t = time.perf_counter()
        with span("wait"):
            jax.block_until_ready(out)
        done = time.perf_counter()
        waits.append(done - t)
        if not more:
            return state, out, len(waits), done - t0, waits
        state, out = nxt_state, nxt_out
        more = not enough(len(waits), done - t0)


def traced_loop(step, state, carry, solves: int, log_dir: str):
    """`solves` solves of :func:`solve_loop` under the profiler, with host
    spans around the window and each dispatch and wait."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("window"):
            return solve_loop(step, state, carry, lambda n, _: n >= solves - 1,
                              jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()


def log_waits(waits) -> None:
    w = sorted(waits)
    log(f"solves {len(w)}, wait s: median {w[len(w) // 2]:.4f}, "
        f"max {w[-1]:.4f}, min {w[0]:.4f}")


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader reads."""
    trace: trace_reduce.Trace
    solves: int
    work: dict
    least_seconds: float


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, overrides: dict | None = None,
        keep_trace: str | None = None, solve=None) -> dict:
    """One run; returns the result line. `overrides` replaces configuration
    keys and `solve` the app's solve (tests run a small or broken cell on
    the CPU with ``require_tpu=False``); `keep_trace` is a directory the
    ``--trace 1`` run's ``.xplane.pb`` is copied into."""
    cell = load_cell(workload, overrides)
    devices = chips(cell.chips, require_tpu)
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    app, cfg = cell.app, cell.cfg
    mesh = app.make_mesh(cell.mesh, devices)
    solve = solve or app.solve

    def step(state):
        return solve(cfg, mesh, state)

    def carry(state, out):
        return app.carry(out) if cell.traffic["carry"] == "output" else state

    counter = CompileCounter()
    t_chips = time.perf_counter()
    state = jax.block_until_ready(
        app.make_input(cfg, cell.traffic, mesh, generate.seed_key(seed)))
    t_input = time.perf_counter()
    out = jax.block_until_ready(step(state))              # warm-up
    state = carry(state, out)
    del out
    setup_s = time.perf_counter() - T_START
    log(f"set-up s: import {T_IMPORTED - T_START:.3f}, chips "
        f"{t_chips - T_IMPORTED:.3f}, input {t_input - t_chips:.3f}, warm-up "
        f"{T_START + setup_s - t_input:.3f}; compile events {counter.events}")
    compiles_before = counter.events
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {}}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not trace:
        state, out, n, elapsed, waits = solve_loop(
            step, state, carry, lambda _, secs: secs >= seconds)
        log(f"compiles in window: {counter.events - compiles_before}")
        log_waits(waits)
        result["attempted"] = n
        peak = peak_bytes(devices)
        values = {"solve_s": elapsed / n, "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] in values:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
    else:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            state, out, n, _, waits = traced_loop(
                step, state, carry, TRACE_SOLVES, log_dir)
            log(f"compiles in window: {counter.events - compiles_before}")
            log_waits(waits)
            result["attempted"] = n
            peak = peak_bytes(devices)
            tr = trace_reduce.load(log_dir, [d.id for d in devices])
            if keep_trace:
                Path(keep_trace).mkdir(parents=True, exist_ok=True)
                shutil.copy(trace_reduce.find_xplane(log_dir),
                            Path(keep_trace) / f"{workload}.{seed}.xplane.pb")
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        work = app.work(cfg)
        ctx = LayerContext(tr, n, work, peaks.least_seconds(work, dev.device_kind))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s)
        result["breakdown"] = tr.breakdown()
    device["memory_peak_bytes"] = peak
    result["device"] = device

    # the reference runs once the window has closed and the peak is read
    t_ref = time.perf_counter()
    numbers = app.compare(out, app.reference(cfg, mesh, state))
    log(f"reference and comparison s: {time.perf_counter() - t_ref:.3f}")
    checks = {k: {"value": v, "limit": cell.limits[k]} for k, v in numbers.items()}
    wrong = [k for k, c in checks.items()
             if not (math.isfinite(c["value"]) and c["value"] <= c["limit"])]
    result["correct"] = not wrong
    result["failed"] = 1 if wrong else 0
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
