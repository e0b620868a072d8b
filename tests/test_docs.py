"""First-class docs stay truthful: README/docs exist, and the benchmark
table committed in docs/overlap.md is EXACTLY what benchmarks.docs_sync
renders from the committed BENCH_quick.json (regenerate both with
``python -m benchmarks.run --quick --update-docs``). Fast, non-slow."""
from __future__ import annotations

from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def test_readme_exists_and_covers_the_basics():
    text = (REPO / "README.md").read_text()
    # quickstart commands must mention the tier-1 verify + bench invocations
    assert "python -m pytest -x -q" in text
    assert "python -m benchmarks.run" in text
    # the paper-concept -> module map must point at the real modules
    for mod in ("core/halo.py", "core/domain.py", "core/reduction.py",
                "runtime/trainer.py", "launch/mesh.py"):
        assert mod in text, f"README concept map lost {mod}"


def test_overlap_doc_exists_and_names_the_knobs():
    text = (REPO / "docs" / "overlap.md").read_text()
    for knob in ("two_phase", "hdot", "subdomains", "grad_buckets",
                 "halo_scan_nd", "make_grid_mesh"):
        assert knob in text, f"docs/overlap.md lost {knob}"


def test_bench_table_not_stale():
    """The generated table region must match a fresh render of the committed
    BENCH_quick.json — fails when one is updated without the other."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    committed = docs_sync.docs_table()
    assert committed is not None, "docs/overlap.md lost its BENCH_TABLE markers"
    rendered = docs_sync.render_table(quick)
    assert committed == rendered, (
        "docs/overlap.md benchmark table is stale relative to "
        "BENCH_quick.json — run `python -m benchmarks.run --quick "
        "--update-docs` and commit both")


def test_bench_quick_tracks_2d_mesh_rows():
    """The committed trajectory must include `mesh_shape` rows for heat2d and
    hpccg (the 2x2-vs-4x1 overlap gap is tracked from PR 3 onward), the RK3
    (y, z) 2x2 mesh, and HPCCG's native 3-D 2x2x2 mesh (PR 4 onward)."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()

    def meshes(suite):
        return {r.get("mesh_shape") for r in quick[suite]["rows"]
                if "mesh_shape" in r}

    for suite in ("heat2d", "hpccg"):
        assert {"2x2", "4x1"} <= meshes(suite), (suite, meshes(suite))
    assert "2x2" in meshes("creams"), meshes("creams")
    assert "2x2x2" in meshes("hpccg"), meshes("hpccg")


def test_bench_quick_rows_carry_provenance():
    """Every BENCH_quick row records the worker's jax version and device
    count — CI artifacts from different runners are only comparable with
    the toolchain pinned to the row."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    for suite, rec in quick.items():
        for r in rec.get("rows", []):
            assert r.get("jax_version"), (suite, r)
            assert r.get("device_count") == r.get("devices"), (suite, r)


def test_render_table_shape():
    from benchmarks import docs_sync

    quick = {"demo": {"rows": [
        {"devices": 4, "mesh_shape": "2x2", "metric": "sweeps_per_s",
         "two_phase": 10.0, "hdot": 8.0, "hdot_two_phase_ratio": 0.8},
        {"devices": 2, "metric": "sweeps_per_s",
         "two_phase": 5.0, "hdot": 5.5, "hdot_two_phase_ratio": 1.1,
         "fsdp": 4.5, "fsdp_two_phase_ratio": 0.9},
    ]}, "mem": {"rows": [
        {"devices": 4, "metric": "peak_live_param_bytes",
         "streaming": 625280.0, "gather_all": 1579904.0,
         "mem_saving_ratio": 2.5266},
    ]}, "broken": {"error": "boom"}}
    table = docs_sync.render_table(quick)
    lines = table.splitlines()
    assert lines[0].startswith("| suite ")
    assert ("| demo | 4 | 2x2 | sweeps_per_s | 10.00 | 8.00 | 0.80x | - |"
            in lines)
    assert ("| demo | 2 | - | sweeps_per_s | 5.00 | 5.50 | 1.10x | 0.90x |"
            in lines)
    assert ("| mem | 4 | - | peak_live_param_bytes | 1579904 | 625280 "
            "| 2.53x | - |" in lines)
    assert any("ERROR" in ln for ln in lines)


def test_bench_quick_tracks_moe_row():
    """The committed trajectory must carry the MoE EP suite (PR 7 onward):
    capacity-chunked a2a_scan (moe_a2a_chunks=2) vs the monolithic
    dispatch/combine, with the headline ratio gated by ci_gate."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    rows = quick["moe"]["rows"]
    assert rows, "moe suite lost its rows"
    assert all(r["metric"] == "steps_per_s" for r in rows), rows
    assert "hdot_two_phase_ratio" in quick["moe"]


def test_bench_quick_tracks_serve_row():
    """The committed trajectory must carry the serving suite (PR 8 onward):
    continuous batching (hdot) vs wave scheduling (two_phase) tokens/s on
    the same Poisson trace, with the ratio gated by ci_gate. The benchmark
    itself asserts continuous > wave; the committed row must agree."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    rows = quick["serve"]["rows"]
    assert rows, "serve suite lost its rows"
    assert all(r["metric"] == "tokens_per_s" for r in rows), rows
    assert quick["serve"]["hdot_two_phase_ratio"] > 1.0, quick["serve"]


def test_overlap_doc_covers_serving():
    text = (REPO / "docs" / "overlap.md").read_text()
    for ref in ("run_continuous", "decode_step_fn", "build_decode_step",
                "lm_decode_tp"):
        assert ref in text, f"docs/overlap.md lost {ref}"


def test_bench_quick_tracks_fsdp_row():
    """lm_step's committed trajectory must carry the ZeRO-3 composition row
    (PR 5 onward) so the fsdp/two_phase headline is gated by ci_gate."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    rows = [r for r in quick["lm_step"]["rows"] if "fsdp_two_phase_ratio" in r]
    assert rows, "lm_step lost its fsdp row"
    assert "fsdp_two_phase_ratio" in quick["lm_step"]


def test_bench_quick_tracks_rebalance_row():
    """The committed trajectory must carry the dynamic re-partitioning drill
    (PR 9 onward): static uniform cut (two_phase slot) vs measured-cost
    re-cut (hdot slot) steps/s under one jax device — the parallelism is OS
    processes. The drill converges near the weighted-balance bound, so the
    committed ratio must show a real recovery, not noise."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    rows = quick["rebalance"]["rows"]
    assert rows, "rebalance suite lost its rows"
    assert all(r["metric"] == "steps_per_s" for r in rows), rows
    assert all(r["devices"] == 1 for r in rows), rows
    assert quick["rebalance"]["hdot_two_phase_ratio"] > 1.2, quick["rebalance"]


def test_bench_quick_tracks_fsdp_mem_row():
    """The committed trajectory must carry the streaming ZeRO-3 memory probe
    (PR 10 onward): per-device peak live param bytes, streaming vs the
    top-of-step gather-all, with losses bit-identical and the streaming peak
    within the shard + fsdp_working_set bound. ci_gate fails when the saving
    ratio dips to 1 or below."""
    from benchmarks import docs_sync

    quick = docs_sync.load_quick()
    rows = quick["fsdp_mem"]["rows"]
    assert rows, "fsdp_mem suite lost its rows"
    assert all(r["metric"] == "peak_live_param_bytes" for r in rows), rows
    assert all(r["loss_bit_equal"] for r in rows), rows
    assert all(r["within_working_set_bound"] for r in rows), rows
    assert all(r["streaming"] < r["gather_all"] for r in rows), rows
    assert quick["fsdp_mem"]["mem_saving_ratio"] > 1.0, quick["fsdp_mem"]


def test_overlap_doc_covers_streaming_zero3():
    text = (REPO / "docs" / "overlap.md").read_text()
    for ref in ("fsdp_streaming", "fsdp_working_set", "train_loss_streamed",
                "restore_fsdp_checkpoint", "lm_fsdp_streaming",
                "AG-ADJACENCY", "fsdp_init_state"):
        assert ref in text, f"docs/overlap.md lost {ref}"


def test_overlap_doc_covers_rebalancing():
    text = (REPO / "docs" / "overlap.md").read_text()
    for ref in ("rebalance_every", "chunk_weights", "CostModel",
                "straggler_drill", "heat2d_weighted", "part_extents",
                "reassign_host_shards"):
        assert ref in text, f"docs/overlap.md lost {ref}"
