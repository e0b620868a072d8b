"""The stage split (``bench/stages.py``) on a hand-made XSpace that carries
``tf_op`` metadata, on the traces recorded before the solvers named their
stages, and on a one-chip Heat2D trace recorded with them."""
from __future__ import annotations

from pathlib import Path

import pytest
from jax.profiler import ProfileData

import stages
import trace_reduce as tr

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PS = 10 ** 12                               # picoseconds a second

# (event name, tf_op path or None, start s, end s) on TPU 0's "XLA Ops"
OPS = [
    ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     "jit(local)/while/body/closed_call/hdot.faces/mul:", 0.5, 1.0),
    ("%concatenate.2 = f32[8]{0} concatenate(f32[4]{0} %a, f32[4]{0} %b)",
     "jit(local)/while/body/hdot.interior/hdot.assemble/concatenate:", 1.0, 2.0),
    ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     "jit(local)/while/body/hdot.interior/add:", 2.0, 3.5),
    ("%copy.4 = f32[8]{0} copy(f32[8]{0} %p)", None, 3.5, 4.0),
    ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     "jit(local)/hdot.exchange/pad:", 4.0, 4.25),
    ("%all-reduce.6 = f32[] all-reduce(f32[] %s), to_apply=%sum",
     "jit(local)/hdot.update/hdot.reduce/psum:", 4.25, 4.5),
    ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop",
     "jit(local)/hdot.update/sub:", 4.5, 5.0),
    ("%while.8 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b",
     "jit(local)/while:", 0.5, 4.0),
    ("%fusion.9 = f32[8]{0} fusion(f32[8]{0} %r), kind=kLoop",
     "jit(local)/hdot.faces/mul:", 5.0, 5.5),
    ("%fusion.10 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop",
     "jit(local)/hdot.faces/add:", 5.5, 6.0),
    # fusions XLA gave no path of their own: their stage is in the HLO
    ("%fusion.11 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
     "jit(local)/while:", 6.0, 6.5),
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", None, 6.5, 7.0),
]
# a second metadata entry for fusion.9 with another path: fusion.9 is then
# unscoped; fusion.10's second entry repeats its path, so it keeps its stage
SECOND_PATHS = {OPS[8][0]: "jit(local)/hdot.interior/mul:",
                OPS[9][0]: OPS[9][1]}
HOST = [("window", 0.0, 8.0), ("dispatch", 0.0, 0.45),
        ("hdot.solve", 0.1, 0.4), ("wait", 0.45, 6.5)]
PROGRAM = 7


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _instr(iid, name, opcode, path="", operands=(), called=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if path:
        fields.append((7, _msg((2, path))))
    if operands:
        fields.append((36, b"".join(_varint(o) for o in operands)))
    if called:
        fields.append((38, b"".join(_varint(c) for c in called)))
    return _msg(*fields)


def hlo_proto() -> bytes:
    """The program's HLO: fusion.11's fused root (an in-place update with no
    path) takes the interior stage of its nearest staged operand, not the
    faces stage one step further; fusion.12's reaches ``hdot.update``
    through a copy; fusion.9's fused computation is staged, but its name
    has two paths."""
    def comp(cid, root, *instrs):
        return _msg(*[(2, i) for i in instrs], (5, cid), (6, root))

    entry = comp(1, 12,
                 _instr(10, "fusion.11", "fusion", "jit(local)/while", called=[2]),
                 _instr(11, "fusion.12", "fusion", called=[3]),
                 _instr(12, "fusion.9", "fusion", called=[4]))
    fused11 = comp(2, 20,
                   _instr(20, "dynamic-update-slice.1", "dynamic-update-slice",
                          operands=[21, 22]),
                   _instr(21, "param_0", "parameter"),
                   _instr(22, "sub.1", "subtract",
                          "jit(local)/while/body/hdot.interior/sub", operands=[23]),
                   _instr(23, "mul.1", "multiply", "jit(local)/hdot.faces/mul"))
    fused12 = comp(3, 30,
                   _instr(30, "add.2", "add", operands=[31]),
                   _instr(31, "copy.2", "copy", operands=[32]),
                   _instr(32, "mul.2", "multiply", "jit(local)/hdot.update/mul"))
    fused9 = comp(4, 40, _instr(40, "mul.3", "multiply", "jit(local)/hdot.faces/mul"))
    module = _msg((1, "jit_local"), *[(3, c) for c in (entry, fused11, fused12, fused9)])
    return _msg((1, module))


def _q(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def _qbytes(b: bytes) -> str:
    return "".join(f"\\{c:03o}" for c in b)


def synthetic_xspace() -> bytes:
    """One chip running one program over [0.5, 6] s; its ops as in `OPS`.
    fusion.1's path is a reference to a stat metadata's name, the others
    are strings; copy.4 and fusion.12 have no ``tf_op``. The program's HLO
    is :func:`hlo_proto`."""
    meta, ops = [], []
    for i, (name, path, a, b) in enumerate(OPS, start=10):
        stats = f"stats {{ metadata_id: 3 uint64_value: {PROGRAM} }}"
        if path is not None and i == 10:
            stats += " stats { metadata_id: 1 ref_value: 2 }"
        elif path is not None:
            stats += f' stats {{ metadata_id: 1 str_value: "{_q(path)}" }}'
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} name: "{_q(name)}" '
                    f'{stats} }} }}')
        ops.append(f"events {{ metadata_id: {i} offset_ps: {int(a * PS)} "
                   f"duration_ps: {int((b - a) * PS)} }}")
    for j, (name, path) in enumerate(SECOND_PATHS.items(), start=100):
        meta.append(f'event_metadata {{ key: {j} value {{ id: {j} name: "{_q(name)}" '
                    f'stats {{ metadata_id: 1 str_value: "{_q(path)}" }} }} }}')
    host_meta = [f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                 for k, (n, _, _) in enumerate(HOST, start=1)]
    host_events = [f"events {{ metadata_id: {k} offset_ps: {int(a * PS)} "
                   f"duration_ps: {int((b - a) * PS)} }}"
                   for k, (_, a, b) in enumerate(HOST, start=1)]
    text = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {PS // 2} duration_ps: {int(5.5 * PS)} }} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {' '.join(ops)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_local(1)" }} }}
  {' '.join(meta)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2
    name: "{_q(OPS[0][1])}" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "program_id" }} }}
}}
planes {{
  id: 3 name: "/host:metadata"
  event_metadata {{ key: {PROGRAM} value {{ id: {PROGRAM} name: "jit_local({PROGRAM})"
    stats {{ metadata_id: 1 bytes_value: "{_qbytes(hlo_proto())}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {' '.join(host_events)} }}
  {' '.join(host_meta)}
}}
"""
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture
def synthetic(tmp_path):
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(synthetic_xspace())
    return stages.load(str(path))


def test_op_metadata_reads_paths_and_programs():
    planes = stages.op_metadata(synthetic_xspace())
    assert list(planes) == ["/device:TPU:0"]         # host planes are skipped
    ops = planes["/device:TPU:0"]
    assert ops[OPS[0][0]] == (OPS[0][1], PROGRAM)    # by reference
    assert ops[OPS[2][0]] == (OPS[2][1], PROGRAM)    # by string
    assert ops[OPS[3][0]] == ("", PROGRAM)           # no tf_op
    assert ops[OPS[8][0]][0] is None                 # two paths disagree
    assert ops[OPS[9][0]][0] == OPS[9][1]            # two paths agree


def test_fused_stages_read_the_programs_hlo():
    assert stages.fused_stages(synthetic_xspace()) == {
        PROGRAM: {"fusion.11": "interior", "fusion.12": "update",
                  "fusion.9": "faces"}}


@pytest.mark.parametrize("path,stage", [
    ("jit(local)/while/body/closed_call/hdot.faces/mul:", "faces"),
    ("jit(local)/hdot.interior/hdot.assemble/concatenate:", "assemble"),
    ("jit(local)/hdot.update/hdot.reduce/psum:", "reduce"),
    ("jit(local)/shard_map/hdot.exchange:", "exchange"),
    ("jit(local)/while/body/dynamic_update_slice:", "unscoped"),
    ("jit(local)/hdot.solve/add:", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_stage_of(path, stage):
    assert stages.stage_of(path) == stage


def test_synthetic_stage_split(synthetic):
    st = synthetic
    dev = st.trace.devices[0]
    assert len(dev.ops) == 11                        # the while is left out
    expected = {"faces": 1.0, "interior": 2.0, "assemble": 1.0,
                "exchange": 0.25, "reduce": 0.25, "update": 1.0,
                "unscoped": 1.0}                     # copy.4 and fusion.9
    for stage, secs in expected.items():
        assert st.stage_s(dev, stage) == pytest.approx(secs, abs=1e-9)
    ms = st.stage_ms(solves=2)
    assert ms == pytest.approx({k: 1e3 * v / 2 for k, v in expected.items()})
    assert sum(ms.values()) == pytest.approx(1e3 * st.trace.busy_s() / 2)


def test_synthetic_idle_gaps_name_program_spans(synthetic):
    """The gap before the first op lies inside the solver entry's span,
    which lies inside the harness's dispatch; the one after the last op
    is named by the window alone."""
    gaps = synthetic.idle_gaps()
    assert gaps == [("window", pytest.approx(1.0)), ("hdot.solve", pytest.approx(0.5))]
    # the harness's reducer reads only its own spans, so it names the
    # dispatch there
    assert [g[0] for g in synthetic.trace.idle_gaps()] == ["window", "dispatch"]


def test_split_sums_to_busy(synthetic):
    line = stages.split(synthetic, solves=1)
    assert line["busy_ms"] == pytest.approx(6500.0)
    assert line["sum_over_busy"] == pytest.approx(1.0)
    assert "hdot.solve" in [name for name, _ in line["idle_gaps"]]


@pytest.mark.parametrize("workload", ["heat2d-16k-1chip", "hpccg-512-1x2x2"])
def test_traces_before_the_scopes_are_all_unscoped(workload):
    """A trace of a program without stage scopes splits into `unscoped`
    alone, so no stage is read from it; the harness's reducer reads it as
    before."""
    path = str(FIXTURES / f"{workload}.xplane.pb")
    st, t = stages.load(path), tr.load(path)
    ms = st.stage_ms(solves=3)
    assert list(ms) == ["unscoped"]
    assert ms["unscoped"] == pytest.approx(1e3 * t.busy_s() / 3, rel=1e-9)
    assert st.trace.busy_s() == t.busy_s() and st.trace.window == t.window
    assert st.trace.op_seconds() == t.op_seconds()


def test_heat2d_fixture_with_stages():
    """One chip, 3 traced solves of a 1024^2 grid, 4 sweeps each, recorded
    by ``bench/record_fixture.py`` from the scoped program: the stages sum
    to the busy time, and the solver entry's host span is in the trace. At
    this size the compiler writes the chunks in place, so no concatenate
    (``hdot.assemble``) is left; the full-size solve has them."""
    st = stages.load(str(FIXTURES / "heat2d-16k-1chip-stages.xplane.pb"))
    ms = st.stage_ms(solves=3)
    assert set(ms) == {"faces", "interior", "reduce", "unscoped"}
    busy_ms = 1e3 * st.trace.busy_s() / 3
    assert sum(ms.values()) == pytest.approx(busy_ms, rel=5e-3)
    assert [e.name for e in st.spans].count("hdot.solve") == 3
    assert "hdot.solve" in [name for name, _ in st.idle_gaps()]


def test_hpccg_full_size_trace_with_stages():
    """One chip, 3 traced solves of ``hpccg-512-1chip`` at full size (512^3,
    150 iterations). XLA gives the matvec's fusions, whose concatenate of
    chunk and face outputs became in-place updates, the loop's path alone:
    their stage comes from their fused computations (four interior chunks,
    one face), so the matvec counts as interior and faces, not unscoped."""
    path = str(FIXTURES / "hpccg-512-1chip-stages.xplane.pb")
    st = stages.load(path)
    ms = st.stage_ms(solves=3)
    assert set(ms) == set(stages.STAGES) | {"unscoped"}
    busy_ms = 1e3 * st.trace.busy_s() / 3
    assert sum(ms.values()) == pytest.approx(busy_ms, rel=5e-3)
    assert ms["interior"] > 0.5 * busy_ms
    raw = Path(path).read_bytes()
    ops = stages.op_metadata(raw)["/device:TPU:0"]
    [fused] = stages.fused_stages(raw).values()
    loop_fusions = [n for n, (p, _) in ops.items()
                    if p == "jit(local)/while:" and tr.parse_op(n)[1] == "fusion"]
    assert sorted(fused[tr.parse_op(n)[0].split(":")[0]]
                  for n in loop_fusions) == ["faces"] + ["interior"] * 4


def test_hpccg_1x2x2_fixture_with_stages():
    """Four chips, 3 traced solves of 32^3 per chip, 4 iterations each,
    recorded by ``bench/record_fixture.py --chips 4`` from the scoped
    program: every stage appears on every chip, each chip's stages sum to
    its busy time, and every face permute counts as exchange and every
    allreduce as reduce."""
    path = FIXTURES / "hpccg-512-1x2x2-stages.xplane.pb"
    st = stages.load(str(path))
    assert [d.index for d in st.trace.devices] == [0, 1, 2, 3]
    raw = path.read_bytes()
    metadata, fused = stages.op_metadata(raw), stages.fused_stages(raw)
    for d in st.trace.devices:
        secs = {s: st.stage_s(d, s) for s in stages.STAGES + ("unscoped",)}
        assert all(secs.values())
        assert sum(secs.values()) == pytest.approx(tr.total(st.trace.busy(d)),
                                                   rel=5e-3)
        ops = {tr.parse_op(k)[0]: v
               for k, v in metadata[f"/device:TPU:{d.index}"].items()}
        found = {(e.opcode, stages._stage(ops.get(e.name), e.name, fused))
                 for e in d.collectives()}
        assert found == {("collective-permute", "exchange"), ("all-reduce", "reduce")}
