#!/usr/bin/env python3
"""Per-stage device time of a traced run: every TPU op attributed to the
HDOT stage it was traced under.

    python3 bench/stages.py --workload <cell> --seed <n>   # trace the cell, then split
    python3 bench/stages.py --xplane <file> --solves <n>   # split a recorded trace

The solvers name their stages with ``jax.named_scope`` (``hdot.faces``,
``hdot.interior``, ``hdot.assemble``, ``hdot.exchange``, ``hdot.reduce``,
``hdot.update``; ``src/repro/core/halo.py``). XLA keeps each op's name path
in its metadata, and the profiler writes it into the trace as the ``tf_op``
stat of the op's event metadata, e.g.
``jit(local)/while/body/closed_call/hdot.faces/mul:``. ``ProfileData`` does
not expose metadata stats, so :func:`op_metadata` reads them from the
``.xplane.pb`` bytes with a small protobuf wire-format reader.

An op's stage is the last ``hdot.*`` component of its path (the innermost
scope wins). A fusion XLA gave no name path of its own (a concatenate turned
into in-place updates, say: the profiler then reports the enclosing loop's
path) takes its stage from its fused computation, in the program's HLO that
the trace carries (the ``Hlo Proto`` stats of the ``/host:metadata``
plane): that of the first instruction with a stage, walking from the root
through the operands. Else the op is ``unscoped``: ops XLA inserts (copies,
broadcasts, a loop's bookkeeping) and anything outside the stages. An event
name that one plane maps to two different paths is ``unscoped`` too. A fused
op carries the path of the instruction XLA named it after, usually its
root, so a fusion that spans two stages counts whole under one. The ops are
those ``trace_reduce.load`` reads (containers left out), and the stages of
one chip partition its busy time: their seconds sum to ``Trace.busy``'s.

The host spans read here are the harness's (``window``, ``dispatch``,
``wait``) and the program's (``hdot.solve`` around each solver entry), so
an idle gap is named by the innermost of them, e.g. a recompile or host
stall inside the solver entry as ``hdot.solve``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

BENCH = Path(__file__).resolve().parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402

STAGES = ("faces", "interior", "assemble", "exchange", "reduce", "update")
UNSCOPED = "unscoped"
SCOPE_PREFIX = "hdot."
TF_OP = "tf_op"
PROGRAM_ID = "program_id"
HLO_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"
# fields of the XSpace proto (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES, _PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 1, 2, 4, 5
_META_ID, _META_NAME, _META_STATS = 1, 2, 5   # XEventMetadata / XStatMetadata
_STAT_META_ID, _STAT_U64, _STAT_STR, _STAT_BYTES, _STAT_REF = 1, 3, 5, 6, 7
# fields of the HLO protos (xla/service/hlo.proto)
_HLO_MODULE, _MODULE_COMPUTATIONS = 1, 3       # HloProto / HloModuleProto
_COMP_INSTRUCTIONS, _COMP_ID, _COMP_ROOT = 2, 5, 6
_INSTR_NAME, _INSTR_OPCODE, _INSTR_METADATA, _INSTR_ID = 1, 2, 7, 35
_INSTR_OPERANDS, _INSTR_CALLED, _OP_NAME = 36, 38, 2


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field of one protobuf message: an int
    for a varint, bytes for a length-delimited field or a fixed width."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _name(buf: bytes) -> str:
    return next((v.decode() for f, v in _fields(buf) if f == _META_NAME), "")


def _planes(data: bytes) -> Iterator[Tuple[str, list, Dict[int, str]]]:
    """(name, event metadata, stat names by id) of each plane of a
    serialized XSpace; each event metadata as (id, name, {stat name: the
    stat's fields})."""
    for f, plane in _fields(data):
        if f != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == _PLANE_NAME:
                name = v.decode()
            elif pf == _PLANE_EVENT_META:
                events.append(_map_entry(v)[1])
            elif pf == _PLANE_STAT_META:
                key, meta = _map_entry(v)
                stat_names[key] = _name(meta)
        metas = []
        for meta in events:
            ev_id, ev_name, stats = 0, "", {}
            for mf, v in _fields(meta):
                if mf == _META_ID:
                    ev_id = v
                elif mf == _META_NAME:
                    ev_name = v.decode()
                elif mf == _META_STATS:
                    stat = dict(_fields(v))
                    stats[stat_names.get(stat.get(_STAT_META_ID), "")] = stat
            metas.append((ev_id, ev_name, stats))
        yield name, metas, stat_names


def op_metadata(data: bytes) -> Dict[str, Dict[str, Tuple[Optional[str], int]]]:
    """For each TPU plane: event-metadata name -> (its ``tf_op`` value, ""
    where it has none, and its program id). The path is None where two
    metadata of that name disagree."""
    planes = {}
    for name, metas, stat_names in _planes(data):
        if not tr.DEVICE_PLANE.match(name):
            continue
        ops: Dict[str, Tuple[Optional[str], int]] = {}
        for _, ev_name, stats in metas:
            stat = stats.get(TF_OP, {})
            if _STAT_STR in stat:
                path = stat[_STAT_STR].decode()
            else:
                path = stat_names.get(stat.get(_STAT_REF), "")
            program = stats.get(PROGRAM_ID, {}).get(_STAT_U64, 0)
            if ops.get(ev_name, (path,))[0] != path:
                path = None
            ops[ev_name] = (path, program)
        planes[name] = ops
    return planes


def _ints(value) -> List[int]:
    """A repeated int64 field's entries: packed (bytes) or one varint."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def fused_stages(data: bytes) -> Dict[int, Dict[str, str]]:
    """For each program whose HLO the trace carries: fusion instruction
    name -> the stage of its fused computation (the first instruction with
    a stage, walking from the root through the operands). Fusions with no
    staged instruction are left out."""
    out: Dict[int, Dict[str, str]] = {}
    for name, metas, _ in _planes(data):
        if name != HLO_PLANE:
            continue
        for program, _, stats in metas:
            proto = stats.get(HLO_PROTO, {}).get(_STAT_BYTES)
            if proto:
                out[program] = _fused_stages(proto)
    return out


def _fused_stages(hlo_proto: bytes) -> Dict[str, str]:
    module = next((v for f, v in _fields(hlo_proto) if f == _HLO_MODULE), b"")
    comps = {}                              # id -> (root id, {id: instruction})
    for f, comp in _fields(module):
        if f != _MODULE_COMPUTATIONS:
            continue
        cid, root, instrs = 0, 0, {}
        for cf, v in _fields(comp):
            if cf == _COMP_ID:
                cid = v
            elif cf == _COMP_ROOT:
                root = v
            elif cf == _COMP_INSTRUCTIONS:
                ins = {"operands": [], "called": [], "path": ""}
                for inf, iv in _fields(v):
                    if inf == _INSTR_NAME:
                        ins["name"] = iv.decode()
                    elif inf == _INSTR_OPCODE:
                        ins["opcode"] = iv.decode()
                    elif inf == _INSTR_ID:
                        ins["id"] = iv
                    elif inf == _INSTR_OPERANDS:
                        ins["operands"] += _ints(iv)
                    elif inf == _INSTR_CALLED:
                        ins["called"] += _ints(iv)
                    elif inf == _INSTR_METADATA:
                        ins["path"] = next((m.decode() for mf, m in _fields(iv)
                                            if mf == _OP_NAME), "")
                instrs[ins.get("id", 0)] = ins
        comps[cid] = (root, instrs)
    stages = {}
    for _, instrs in comps.values():
        for ins in instrs.values():
            if ins.get("opcode") != "fusion" or not ins["called"]:
                continue
            root, body = comps.get(ins["called"][0], (0, {}))
            queue, seen = [root], {root}
            while queue:
                cur = body.get(queue.pop(0))
                if cur is None:
                    continue
                stage = stage_of(cur["path"])
                if stage != UNSCOPED:
                    stages[ins["name"]] = stage
                    break
                for o in cur["operands"]:
                    if o not in seen:
                        seen.add(o)
                        queue.append(o)
    return stages


def stage_of(path: Optional[str]) -> str:
    """The innermost ``hdot.*`` scope of an op's name path, or
    ``unscoped``."""
    scopes = [c for c in (path or "").split("/") if c.startswith(SCOPE_PREFIX)]
    stage = scopes[-1][len(SCOPE_PREFIX):].rstrip(":") if scopes else UNSCOPED
    return stage if stage in STAGES else UNSCOPED


@dataclasses.dataclass
class StageTrace:
    """A :class:`trace_reduce.Trace` with each chip's op intervals grouped
    by stage, and the host spans of the harness and of the program."""
    trace: tr.Trace
    stages: Dict[int, Dict[str, List[tr.Interval]]]
    spans: List[tr.Event]

    def stage_s(self, dev: tr.Device, stage: str) -> float:
        """Seconds of the union of `stage`'s op intervals on `dev` in the
        window."""
        found = self.stages.get(dev.index, {}).get(stage, [])
        return tr.total(tr.union(tr.clip(found, *self.trace.window)))

    def stage_ms(self, solves: int) -> Dict[str, float]:
        """ms per solve of each stage that ran an op in the window, averaged
        over the chips."""
        devs = self.trace.devices
        out = {}
        for stage in STAGES + (UNSCOPED,):
            secs = [self.stage_s(d, stage) for d in devs]
            if any(secs):
                out[stage] = 1e3 * sum(secs) / len(devs) / solves
        return out

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """:meth:`trace_reduce.Trace.idle_gaps`, each gap named by the
        innermost span, of the harness or the program, at its middle."""
        t = self.trace
        return tr.Trace(t.devices, self.spans, t.window).idle_gaps()


def load(path: str, device_ids: Optional[Sequence[int]] = None) -> StageTrace:
    """Read the trace at `path` (an ``.xplane.pb`` file or a profiler log
    directory) as :func:`trace_reduce.load` does, with each op's stage."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_xplane(path)
    trace = tr.load(path, device_ids)
    with open(path, "rb") as f:
        raw = f.read()
    metadata = op_metadata(raw)
    fused = fused_stages(raw)
    kept = {d.index for d in trace.devices}
    stages: Dict[int, Dict[str, List[tr.Interval]]] = {}
    spans: List[tr.Event] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in kept:
            ops = metadata.get(plane.name, {})
            acc = stages.setdefault(int(m.group(1)), {})
            for ln in plane.lines:
                if ln.name != tr.OPS_LINE:
                    continue
                for e in ln.events:
                    label, opcode = tr.parse_op(e.name)
                    if opcode in tr.CONTAINER_OPCODES:
                        continue
                    start = e.start_ns * 1e-9
                    acc.setdefault(_stage(ops.get(e.name), label, fused), []).append(
                        (start, start + e.duration_ns * 1e-9))
        elif plane.name == tr.HOST_PLANE:
            for ln in plane.lines:
                spans.extend(tr.Event(e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9)
                             for e in ln.events
                             if e.name in tr.HOST_SPANS
                             or e.name.startswith(SCOPE_PREFIX))
    return StageTrace(trace, stages, spans)


def _stage(meta: Optional[Tuple[Optional[str], int]], label: str,
           fused: Dict[int, Dict[str, str]]) -> str:
    """The stage of one op: from its own path, else from its fused
    computation; ``unscoped`` where its name has two paths."""
    path, program = meta if meta is not None else ("", 0)
    if path is None:
        return UNSCOPED
    stage = stage_of(path)
    if stage == UNSCOPED:
        stage = fused.get(program, {}).get(label.split(":")[0], UNSCOPED)
    return stage


def split(st: StageTrace, solves: int) -> dict:
    """The stage split of `solves` traced solves, beside the busy time it
    must add up to."""
    stage_ms = st.stage_ms(solves)
    busy_ms = 1e3 * st.trace.busy_s() / solves
    return {"solves": solves, "stage_ms": stage_ms, "busy_ms": busy_ms,
            "sum_over_busy": sum(stage_ms.values()) / busy_ms if busy_ms else None,
            "idle_gaps": [[k, v] for k, v in st.idle_gaps()[:10]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--workload", help="a cell of BENCHMARK.json to trace")
    src.add_argument("--xplane", help="a recorded .xplane.pb to split")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--solves", type=int, help="solves in the --xplane trace")
    ap.add_argument("--keep", help="directory the traced run's .xplane.pb "
                                   "is copied into")
    args = ap.parse_args(argv)
    line = {}
    if args.workload:
        import run_cell

        keep = args.keep or tempfile.mkdtemp(prefix="bench-stages-")
        result = run_cell.run(args.workload, args.seed, 0.0, True,
                              keep_trace=keep)
        xplane = os.path.join(keep, f"{args.workload}.{args.seed}.xplane.pb")
        solves = result["attempted"]
        line.update(workload=args.workload, seed=args.seed, result=result)
    else:
        if args.solves is None:
            ap.error("--xplane needs --solves")
        xplane, solves = args.xplane, args.solves
    t0 = time.perf_counter()
    st = load(xplane)
    line.update(split(st, solves), load_s=time.perf_counter() - t0)
    if args.workload and not args.keep:
        os.remove(xplane)
        os.rmdir(os.path.dirname(xplane))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
