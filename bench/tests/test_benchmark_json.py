"""``BENCHMARK.json`` and the files its names lead to, and the command's
refusals: no chip, and a checkout that holds only the benchmark."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import run_cell

ROOT = run_cell.ROOT
BENCH = run_cell.BENCH
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_keys_and_names():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in SPEC["end_to_end"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_cells_and_configurations_are_well_formed():
    cells = SPEC["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs)), "a pair of config and traffic given twice"
    assert {c["name"] for c in SPEC["configs"]} == {w["config"] for w in cells}
    four = sum(w["chips"] == 4 for w in cells)
    assert all(w["chips"] in (1, 4) for w in cells)
    assert four <= max(1, len(cells) // 2)
    for w in cells:
        assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    for c in SPEC["configs"]:
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert c["file"].startswith("bench/")
    names = {w["name"] for w in cells}
    for m in SPEC["per_layer"]:
        assert set(m.get("workloads", names)) <= names


def test_every_name_has_its_files():
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (BENCH / "apps" / f"{cfg['app']}.py").is_file()
    for w in SPEC["workloads"]:
        cell = run_cell.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
    for m in SPEC["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


def test_harness_names_no_cell_or_configuration():
    text = (BENCH / "run_cell.py").read_text()
    for k in ("configs", "workloads"):
        for x in SPEC[k]:
            assert x["name"] not in text


def command(cwd, *extra, env=None):
    args = [sys.executable, "bench/run_cell.py", "--workload", "heat2d-16k-1chip",
            "--seed", "1", "--seconds", "1", "--trace", "0", *extra]
    return subprocess.run(args, cwd=cwd, capture_output=True, text=True,
                          timeout=300, env=env)


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = command(ROOT, env=env)
    assert out.returncode == 2 and out.stdout == "", out.stderr[-2000:]
    assert "no TPU" in out.stderr


def test_benchmark_alone_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    out = command(tmp_path, env=env)
    assert out.returncode != 0 and out.stdout == ""
    assert "No module named 'repro'" in out.stderr


def test_command_runs_from_paths():
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run_cell.py"]
