"""The trace reducer on hand-made intervals, and on small traces recorded on
the chip by ``bench/record_fixture.py`` (one chip: Heat2D; four chips:
HPCCG on a 1x2x2 mesh, whose trace holds collectives)."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

import peaks
import run_cell
import trace_reduce as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_union_subtract_clip():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    assert tr.subtract([(0, 10)], [(1, 2), (5, 6)]) == [(0, 1), (2, 5), (6, 10)]
    assert tr.subtract([(1, 2)], [(0, 3)]) == []
    assert tr.clip([(0, 5), (6, 7)], 1, 6.5) == [(1, 5), (6, 6.5)]
    assert tr.total(tr.union([(0, 2), (1, 3)])) == 3


@pytest.mark.parametrize("text,label,opcode", [
    ("collective-permute-done.3", "collective-permute-done.3", "collective-permute"),
    ("all-reduce.12", "all-reduce.12", "all-reduce"),
    ("fusion.123", "fusion.123", "fusion"),
    ("%fusion.92 = f32[512,512,512]{1,0,2:T(8,128)} fusion(f32[512,512,512]{1,0,2:T(8,128)}"
     " %fusion.91), kind=kLoop, calls=%fused_computation.13.clone.clone",
     "fusion.92: fusion f32[512,512,512]", "fusion"),
    ("%while = (s32[]{:T(128)}, f32[16384,16384]{1,0:T(8,128)}) while((s32[]{:T(128)}, "
     "f32[16384,16384]{1,0:T(8,128)}) %tuple.96), condition=%c, body=%b",
     "while: while", "while"),
    ("%copy-start = (f32[4096,4096]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) copy-start("
     "f32[4096,4096]{1,0:T(8,128)} %x)", "copy-start: copy-start", "copy"),
    ("%collective-permute-start.4 = (f32[514,512,1]{1,2,0:T(1,128)}, u32[]{:S(2)}) "
     "collective-permute-start(%copy.33), channel_id=6, source_target_pairs={{0,1},{2,3}}",
     "collective-permute-start.4: collective-permute-start", "collective-permute"),
])
def test_parse_op(text, label, opcode):
    assert tr.parse_op(text) == (label, opcode)


def ev(text, a, b):
    label, opcode = tr.parse_op(text)
    return tr.Event(label, a, b, opcode)


def synthetic():
    ops0 = [ev("fusion.1", 0.0, 2.0), ev("all-reduce.1", 2.0, 3.0),
            ev("collective-permute-done.2", 2.5, 4.0), ev("fusion.2", 3.5, 6.0)]
    ops1 = [ev("fusion.1", 1.0, 5.0), ev("all-reduce.1", 5.0, 6.0)]
    devs = [tr.Device(0, ops0, [ev("jit_solve", 0.0, 6.0)]),
            tr.Device(1, ops1, [ev("jit_solve", 1.0, 6.0)])]
    host = [ev("window", 0.0, 8.0), ev("dispatch", 0.0, 0.2), ev("wait", 0.2, 6.5)]
    return tr.Trace(devs, host, (0.0, 8.0))


def test_synthetic_trace():
    t = synthetic()
    assert t.window_s == 8.0
    assert t.busy_s() == (6.0 + 5.0) / 2
    assert t.collective_s(t.devices[0]) == 2.0          # [2, 4]
    # [2, 4] less the other ops' [0, 2] and [3.5, 6]
    assert t.exposed_collective_s(t.devices[0]) == 1.5
    assert t.exposed_collective_s(t.devices[1]) == 1.0
    assert t.module_s(t.devices[0]) == 6.0 and t.module_s(t.devices[1]) == 5.0
    assert t.idle_gaps() == [("window on TPU 0", 2.0), ("window on TPU 1", 2.0),
                             ("wait on TPU 1", 1.0)]
    bd = t.breakdown(n=2)
    assert bd["device_ops"] == [["fusion.1", 3.0], ["fusion.2", 1.25]]
    assert bd["idle_gaps"] == [["window on TPU 0", 2.0], ["window on TPU 1", 2.0]]


def read(metric, ctx):
    spec = importlib.util.spec_from_file_location(
        metric, run_cell.BENCH / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def test_metric_readers_on_synthetic_trace():
    ctx = run_cell.LayerContext(synthetic(), solves=2, work={}, least_seconds=1.5)
    assert read("mfu", ctx) == pytest.approx(100 * 1.5 / (6.0 / 2))
    assert read("idle_share", ctx) == pytest.approx(100 * (1 - 5.5 / 8))
    assert read("comm_ms", ctx) == pytest.approx(1e3 * (2.0 + 1.0) / 2 / 2)
    assert read("exposed_comm_ms", ctx) == pytest.approx(1e3 * (1.5 + 1.0) / 2 / 2)


def test_comm_readers_find_nothing_without_collectives():
    t = synthetic()
    for d in t.devices:
        d.ops = [e for e in d.ops if e.opcode not in tr.COLLECTIVE_OPCODES]
    ctx = run_cell.LayerContext(t, solves=2, work={}, least_seconds=1.0)
    assert read("comm_ms", ctx) is None and read("exposed_comm_ms", ctx) is None


def sweep_busy(events, lo, hi):
    """Busy seconds by an event-count sweep, independent of union()."""
    inside = [e for e in events if e.end > lo and e.start < hi]
    edges = sorted([(max(e.start, lo), 1) for e in inside]
                   + [(min(e.end, hi), -1) for e in inside], key=lambda x: (x[0], -x[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def fixture(workload):
    path = FIXTURES / f"{workload}.xplane.pb"
    assert path.is_file(), f"{path} is recorded by bench/record_fixture.py"
    return tr.load(str(path))


def test_heat2d_fixture():
    """One chip, 3 traced solves of a 1024^2 grid, 4 sweeps each (recorded
    before the harness queued a solve ahead of each wait: its host plane also
    holds a ``solve`` span per solve, which the reducer does not read)."""
    t = fixture("heat2d-16k-1chip")
    assert [d.index for d in t.devices] == [0]
    dev = t.devices[0]
    assert len(dev.modules) == 3
    assert [e.name for e in t.host].count("wait") == 3
    assert all(t.window[0] <= m.start and m.end <= t.window[1] for m in dev.modules)
    assert not any(e.opcode in tr.CONTAINER_OPCODES for e in dev.ops)
    assert t.busy_s() == pytest.approx(sweep_busy(dev.ops, *t.window), rel=1e-9)
    assert 0 < t.busy_s() <= t.module_s(dev) <= t.window_s
    assert t.collective_s(dev) == 0 and t.exposed_collective_s(dev) == 0
    bd = t.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert sum(v for _, v in t.idle_gaps()) == pytest.approx(t.window_s - t.busy_s())
    cfg = dict(run_cell.load_cell("heat2d-16k-1chip").cfg,
               local_grid=[1024, 1024], sweeps=4)
    work = run_cell.load_cell("heat2d-16k-1chip").app.work(cfg)
    ctx = run_cell.LayerContext(t, 3, work, peaks.least_seconds(work, "TPU v5 lite"))
    assert 0 < read("mfu", ctx) <= 100
    assert 0 < read("idle_share", ctx) < 100


def test_hpccg_1x2x2_fixture():
    """Four chips, 3 traced solves of 32^3 per chip, 4 iterations each: the
    face exchanges and the dot products' allreduces are in the trace."""
    t = fixture("hpccg-512-1x2x2")
    assert [d.index for d in t.devices] == [0, 1, 2, 3]
    assert {e.name for e in t.host} == {"window", "dispatch", "wait"}
    assert [e.name for e in t.host].count("wait") == 3
    for d in t.devices:
        assert len(d.modules) == 3
        assert {e.opcode for e in d.collectives()} == {"all-reduce", "collective-permute"}
        assert sweep_busy(d.ops, *t.window) == pytest.approx(tr.total(t.busy(d)), rel=1e-9)
        assert 0 < t.exposed_collective_s(d) <= t.collective_s(d) < t.window_s
    ctx = run_cell.LayerContext(t, 3, {"flops": 1.0, "bytes": 1.0}, least_seconds=1e-6)
    comm, exposed = read("comm_ms", ctx), read("exposed_comm_ms", ctx)
    assert 0 < exposed <= comm
    assert comm == pytest.approx(
        1e3 * sum(t.collective_s(d) for d in t.devices) / 4 / 3)
    assert 0 < read("idle_share", ctx) < 100
    assert len(t.breakdown()["idle_gaps"]) == 10
    assert any(name.endswith("on TPU 3") for name, _ in t.idle_gaps())
