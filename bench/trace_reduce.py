"""Reduce a JAX profiler trace to the intervals the per-layer metrics read.

The profiler writes an ``.xplane.pb`` file; ``jax.profiler.ProfileData``
reads it. On a TPU each chip is a plane named ``/device:TPU:<n>``. Its
``XLA Modules`` line holds one event per program run; its ``XLA Ops`` line
one event per op the core ran, named by the op's HLO text
(``%fusion.92 = f32[512,512,512]{...} fusion(...)``), with a loop's
``while`` op spanning the ops of its body; its ``Async XLA Ops`` line one
event per asynchronous copy or collective, from its start to its done. The
host plane (``/host:CPU``) holds the ``jax.profiler.TraceAnnotation`` spans
the harness puts around the traced window and each dispatch and wait. Host
and device events share one clock to within about a millisecond: in
recorded traces a program run can start up to that much before the host
span of its dispatch.

Only these intervals are kept, in seconds. Every quantity below is computed
inside the traced window: the host span named ``window``, widened to cover
every program run in the trace (the profiler runs only around the traced
solves, so every run in it is one of theirs). An op that contains others
(``while``, ``conditional``, ``call``) is left out of the ops, so that busy
time is the time the core ran an op.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
# the spans the harness records on the host
HOST_SPANS = ("window", "dispatch", "wait")
# HLO opcodes that move data between chips (async pairs end in -start/-done)
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all",
                      "collective-broadcast", "send", "recv")
CONTAINER_OPCODES = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_HLO_OPCODE = re.compile(r"\s(?P<op>[a-z][a-z0-9-]*)\(")
_NAMED = re.compile(r"^(?P<op>[a-z][a-z0-9-]*?)(\.\d+)*$")
_ASYNC_SUFFIX = re.compile(r"-(start|done|update)$")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # seconds
    end: float              # seconds
    opcode: str = ""

    @property
    def seconds(self) -> float:
        return self.end - self.start


def parse_op(text: str) -> Tuple[str, str]:
    """(label, opcode) of an op event. An event named by its HLO text is
    labelled ``<instruction>: <opcode> <result type>``; one named by its
    instruction alone (``collective-permute-done.3``) takes its opcode from
    the name. Async suffixes are dropped from the opcode."""
    m = _HLO.match(text)
    if m:
        rest = " " + m.group("rest")
        o = _HLO_OPCODE.search(rest)
        op = o.group("op") if o else m.group("name")
        kind = rest[:o.start()].strip() if o else ""
        label = f"{m.group('name')}: {op}"
        if kind and not kind.startswith("("):
            label += " " + kind.split("{")[0]
    else:
        n = _NAMED.match(text)
        op, label = (n.group("op") if n else text), text
    return label, _ASYNC_SUFFIX.sub("", op)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(intervals: Sequence[Interval],
             cover: Sequence[Interval]) -> List[Interval]:
    """The parts of `intervals` that no interval of `cover` overlaps
    (`cover` sorted and disjoint, as :func:`union` returns it)."""
    out = []
    for a, b in intervals:
        cur = a
        for c, d in cover:
            if d <= cur:
                continue
            if c >= b:
                break
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


@dataclasses.dataclass
class Device:
    """One chip's events: the ops its core ran (containers left out), its
    program runs, and its asynchronous copies and collectives from start to
    done."""
    index: int
    ops: List[Event]
    modules: List[Event]
    in_flight: List[Event] = dataclasses.field(default_factory=list)

    def collectives(self) -> List[Event]:
        return [e for e in self.ops + self.in_flight
                if e.opcode in COLLECTIVE_OPCODES]

    def computing(self) -> List[Event]:
        return [e for e in self.ops if e.opcode not in COLLECTIVE_OPCODES]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Event]
    window: Interval

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _in_window(self, events: Iterable[Event]) -> List[Interval]:
        return clip(((e.start, e.end) for e in events), *self.window)

    def busy(self, dev: Device) -> List[Interval]:
        """Union of the intervals in which an op ran on `dev`."""
        return union(self._in_window(dev.ops))

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        return sum(total(self.busy(d)) for d in self.devices) / len(self.devices)

    def module_s(self, dev: Device) -> float:
        """Seconds of program runs on `dev` inside the window."""
        return total(union(self._in_window(dev.modules)))

    def collective_s(self, dev: Device) -> float:
        """Seconds in which a collective was in flight or ran on `dev`."""
        return total(union(self._in_window(dev.collectives())))

    def exposed_collective_s(self, dev: Device) -> float:
        """The part of :meth:`collective_s` in which the core ran no other
        op."""
        coll = union(self._in_window(dev.collectives()))
        return total(subtract(coll, union(self._in_window(dev.computing()))))

    def op_seconds(self) -> Dict[str, float]:
        """Seconds per op name in the window, averaged over the devices."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for e in d.ops:
                secs = min(e.end, self.window[1]) - max(e.start, self.window[0])
                if secs > 0:
                    acc[e.name] = acc.get(e.name, 0.0) + secs / len(self.devices)
        return acc

    def host_span_at(self, t: float) -> str:
        """Innermost harness span on the host at time `t`."""
        best: Optional[Event] = None
        for e in self.host:
            if e.start <= t <= e.end and (best is None or e.seconds < best.seconds):
                best = e
        return best.name if best else "outside"

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Every idle gap of every device in the window, named by what the
        host was doing at its middle, longest first."""
        gaps = []
        for d in self.devices:
            tag = f" on TPU {d.index}" if len(self.devices) > 1 else ""
            for a, b in subtract([self.window], self.busy(d)):
                gaps.append((self.host_span_at((a + b) / 2) + tag, b - a))
        return sorted(gaps, key=lambda g: -g[1])

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:n]]}


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` file a profiler session wrote under `log_dir`."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def load(path: str, device_ids: Optional[Sequence[int]] = None) -> Trace:
    """Read the trace at `path` (an ``.xplane.pb`` file or a profiler log
    directory). `device_ids` keeps only those chips' planes (default: every
    chip in the trace)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: List[Device] = []
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            idx = int(m.group(1))
            if device_ids is not None and idx not in device_ids:
                continue
            lines = {ln.name: ln for ln in plane.lines}
            ops = [e for e in _events(lines.get(OPS_LINE), ops=True)
                   if e.opcode not in CONTAINER_OPCODES]
            devices.append(Device(idx, ops, _events(lines.get(MODULES_LINE)),
                                  _events(lines.get(ASYNC_LINE), ops=True)))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e.name in HOST_SPANS)
    devices.sort(key=lambda d: d.index)
    windows = [e for e in host if e.name == WINDOW]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    if len(windows) != 1:
        raise ValueError(f"{path}: expected one host span {WINDOW!r}, "
                         f"found {len(windows)}")
    runs = [m for d in devices for m in d.modules]
    return Trace(devices, host, (min([windows[0].start] + [m.start for m in runs]),
                                 max([windows[0].end] + [m.end for m in runs])))


def _events(line, ops: bool = False) -> List[Event]:
    if line is None:
        return []
    out = []
    for e in line.events:
        name, op = parse_op(e.name) if ops else (e.name, "")
        out.append(Event(name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, op))
    return out
