"""Measured-cost dynamic re-partitioning: the weighted N-D partition, the
cost model, the unified solver mesh contract, and the re-cut drivers.

Invariants under test:
  * ``weights=None`` is bit-identical to the historical uniform split (the
    oracle tests elsewhere stay valid unchanged);
  * a weighted cut still covers the extent with contiguous, monotone,
    non-empty parts, and balances summed cost within ``max(weights)`` of the
    total/parts ideal;
  * the canonical cut (``part_extents``) is hashable and idempotent — the
    jitted-solver caches key on it, so an unchanged cut never recompiles;
  * a re-cut never changes the numerics, only the schedule.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.cost import CostModel
from repro.core.domain import (decompose_grid, interior_boxes, interior_cuts,
                               part_extents, split_ranges, _split_extent)
from repro.runtime.ft import reassign_host_shards

extents = st.integers(min_value=1, max_value=64)
parts_st = st.integers(min_value=1, max_value=8)


# ---------------------------------------------------- weighted split (domain)
@given(extent=extents, parts=parts_st)
@settings(max_examples=200, deadline=None)
def test_weights_none_is_uniform(extent, parts):
    assert split_ranges(extent, parts, None) == _split_extent(extent, parts)


@given(extent=extents, parts=parts_st, data=st.data())
@settings(max_examples=200, deadline=None)
def test_weighted_cover_contiguous_monotone(extent, parts, data):
    w = data.draw(st.lists(st.floats(0.0, 10.0), min_size=extent,
                           max_size=extent))
    ranges = split_ranges(extent, parts, w)
    assert len(ranges) == parts
    assert ranges[0][0] == 0 and ranges[-1][1] == extent
    for (a0, b0), (a1, b1) in zip(ranges, ranges[1:]):
        assert b0 == a1          # contiguous, monotone cuts
    if extent >= parts:
        assert all(b > a for a, b in ranges)  # every part keeps >= 1 cell


@given(extent=st.integers(8, 64), parts=st.integers(1, 4), data=st.data())
@settings(max_examples=200, deadline=None)
def test_weighted_balance_bound(extent, parts, data):
    w = data.draw(st.lists(st.floats(0.0, 10.0), min_size=extent,
                           max_size=extent))
    ranges = split_ranges(extent, parts, w)
    total = sum(w)
    worst = max(sum(w[a:b]) for a, b in ranges)
    assert worst <= total / parts + (max(w) if w else 0.0) + 1e-9


def test_flat_weights_collapse_to_uniform():
    """Equal per-cell costs carry no cut preference: the weighted path must
    land exactly on the uniform split, or flat re-measurements would flip
    the cut and recompile for nothing."""
    for extent, parts in ((14, 4), (30, 4), (7, 3), (16, 5)):
        for c in (1.0, 2.5):
            assert (split_ranges(extent, parts, [c] * extent)
                    == _split_extent(extent, parts))
    assert split_ranges(10, 3, [0.0] * 10) == _split_extent(10, 3)


def test_explicit_extents_and_idempotence():
    assert split_ranges(10, 3, (4, 3, 3)) == [(0, 4), (4, 7), (7, 10)]
    for w in (None, (4, 3, 3), [5.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0,
                                1.0, 1.0]):
        cut = part_extents(10, 3, w)
        assert sum(cut) == 10 and len(cut) == 3
        assert part_extents(10, 3, cut) == cut  # canonical form is a fixpoint


def test_weighted_split_validation():
    with pytest.raises(ValueError):
        split_ranges(10, 3, [1.0] * 7)          # wrong length
    with pytest.raises(ValueError):
        split_ranges(10, 3, [-1.0] + [1.0] * 9)  # negative cost
    with pytest.raises(ValueError):
        split_ranges(10, 2, (11, -1))           # negative explicit extent
    with pytest.raises(ValueError):
        split_ranges(10, 0)


def test_skewed_weights_shift_the_cut():
    """Mass on the left yields smaller left parts (equal-cost parts)."""
    w = [4.0] * 8 + [1.0] * 24
    cut = part_extents(32, 4, w)
    assert cut[0] < cut[-1]
    assert sum(cut) == 32


# ------------------------------------------------ weighted interior chunking
def test_interior_boxes_weighted_cover_and_none_identity():
    shape, width, grid = (20, 18), 1, (3, 2)
    uniform = interior_boxes(shape, width, grid)
    assert interior_boxes(shape, width, grid, weights=None) == uniform
    w = ([5.0] * 6 + [1.0] * 12, None)
    boxes = interior_boxes(shape, width, grid, weights=w)
    cover = np.zeros(shape, np.int32)
    for b in boxes:
        cover[b.slices()] += 1
    interior = cover[width:-width, width:-width]
    assert (interior == 1).all()
    assert cover.sum() == interior.size  # nothing leaks into the halo frame


@pytest.mark.parametrize("shape,width,grid,weights,quantum", [
    ((64, 512), 1, (3, 2), None, (8, 128)),
    ((64, 512), 1, (3, 2), ([5.0] * 10 + [1.0] * 52, None), (8, 128)),
    ((64, 512), 1, (3, 2), ((2, 9, 51), (300, 210)), (8, 128)),
    ((96, 512), 2, (4, 2), ((1, 1, 1, 89), None), (16, 128)),
    ((20, 18), 1, (3, 2), ([5.0] * 6 + [1.0] * 12, None), (1, 1)),
])
def test_interior_boxes_snapped_to_quantum(shape, width, grid, weights,
                                           quantum):
    """A cut snapped to a quantum keeps its chunk count and order, puts
    every cut on a multiple within `tile_span`, and moves no cut by more
    than half a quantum (or to the span's ends); a quantum of 1 is the
    unsnapped cut."""
    from repro.core.domain import tile_span

    plain = interior_boxes(shape, width, grid, weights)
    snapped = interior_boxes(shape, width, grid, weights, quantum)
    assert len(snapped) == len(plain)
    spans = [tile_span(n, width, q) for n, q in zip(shape, quantum)]
    for p, s in zip(plain, snapped):
        for x, y, q, (lo, hi) in zip(p.start + p.stop, s.start + s.stop,
                                     quantum * 2, spans * 2):
            assert y % q == 0 and lo <= y <= hi
            assert abs(y - x) <= q // 2 or y in (lo, hi)
    if set(quantum) == {1}:
        assert snapped == plain


def test_interior_cuts_matches_boxes():
    shape, width, grid = (20, 18), 1, (3, 2)
    w = ([5.0] * 6 + [1.0] * 12, None)
    cuts = interior_cuts(shape, width, grid, weights=w)
    boxes = interior_boxes(shape, width, grid, weights=w)
    dim0 = sorted({(b.start[0], b.stop[0]) for b in boxes})
    assert tuple(b - a for a, b in dim0) == cuts[0]
    assert sum(cuts[0]) == shape[0] - 2 * width
    assert sum(cuts[1]) == shape[1] - 2 * width


# ----------------------------------------------------------------- CostModel
def test_cost_model_ema_and_normalization():
    cm = CostModel(alpha=0.5)
    assert cm.record("k", 10.0, cells=10) == pytest.approx(1.0)
    assert cm.record("k", 30.0, cells=10) == pytest.approx(2.0)  # 0.5/0.5 mix
    assert cm.ema("k") == pytest.approx(2.0)
    assert cm.observations("k") == 2 and len(cm) == 1
    assert cm.ema("missing", default=7.0) == 7.0
    with pytest.raises(ValueError):
        cm.record("k", -1.0)
    with pytest.raises(ValueError):
        CostModel(alpha=0.0)


def test_cost_model_weights_along_marginalizes():
    """Two chunks along dim 0 (rates 3 and 1) -> the dim-0 per-cell profile
    is hot then cold, and the next cut shrinks the hot chunk; unmeasured
    chunks fall back to the mean-rate prior."""
    cm = CostModel(alpha=1.0)
    ranges = [[(0, 8), (8, 16)], [(0, 10)]]
    cm.record((0, 0), 3.0 * 8 * 10, cells=80)
    cm.record((1, 0), 1.0 * 8 * 10, cells=80)
    prof = cm.weights_along(ranges)
    assert prof[0][:8] == (3.0,) * 8 and prof[0][8:] == (1.0,) * 8
    assert prof[1] == (2.0,) * 10  # dim-1 averages over both dim-0 chunks
    cut = part_extents(16, 2, prof[0])
    assert cut[0] < cut[1]

    empty = CostModel()
    assert empty.mean_rate() == 1.0
    prof0 = empty.weights_along(ranges)
    assert prof0[0] == (1.0,) * 16  # prior only -> flat -> uniform cut
    assert part_extents(16, 2, prof0[0]) == part_extents(16, 2, None)


# ----------------------------------------------- unified solver mesh contract
def test_normalize_mesh_axes_contract():
    import repro.core.stencil as stencil

    norm = stencil.normalize_mesh_axes
    assert norm(("data",), "heat2d_solve", (1, 2)) == ("data",)
    assert norm(["rows", "cols"], "heat2d_solve", (1, 2)) == ("rows", "cols")

    # a bare string is out of contract: no longer coerced to one axis
    with pytest.raises(ValueError, match="heat2d_solve.*tuple"):
        norm("data", "heat2d_solve", (1, 2))

    with pytest.raises(ValueError, match="hpccg_solve.*1 or 2 or 3"):
        norm(("a", "b", "c", "d"), "hpccg_solve", (1, 2, 3))
    with pytest.raises(ValueError, match="rk3_solve"):
        norm((), "rk3_solve", (1, 2))
    with pytest.raises(ValueError, match="repeats"):
        norm(("data", "data"), "heat2d_solve", (1, 2))
    with pytest.raises(ValueError, match="axis names"):
        norm(("data", 1), "heat2d_solve", (1, 2))
    with pytest.raises(ValueError):
        norm(42, "heat2d_solve", (1, 2))


# --------------------------------------------- solver re-cut (single device)
def test_heat2d_chunk_weights_numerics_and_cache(single_mesh):
    from repro.core.stencil import _heat2d_solver, heat2d_init, heat2d_solve

    u0 = heat2d_init(32, 32)
    ref, res_ref = heat2d_solve(u0, single_mesh, ("data",), 6, "hdot", 4)
    n0 = _heat2d_solver.cache_info().currsize

    # uniform per-cell costs collapse onto the unweighted program
    u1, _ = heat2d_solve(u0, single_mesh, ("data",), 6, "hdot", 4,
                         chunk_weights=([1.0] * 30,))
    assert _heat2d_solver.cache_info().currsize == n0
    np.testing.assert_array_equal(np.asarray(u1), np.asarray(ref))

    # a skewed cut recompiles exactly once, then caches
    skew = ([9.0] * 8 + [1.0] * 22,)
    u2, _ = heat2d_solve(u0, single_mesh, ("data",), 6, "hdot", 4,
                         chunk_weights=skew)
    n1 = _heat2d_solver.cache_info().currsize
    assert n1 == n0 + 1
    u3, _ = heat2d_solve(u0, single_mesh, ("data",), 6, "hdot", 4,
                         chunk_weights=skew)
    assert _heat2d_solver.cache_info().currsize == n1
    np.testing.assert_allclose(np.asarray(u2), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(u2), np.asarray(u3))

    with pytest.raises(ValueError, match="chunk_weights"):
        heat2d_solve(u0, single_mesh, ("data",), 2, "hdot", 4,
                     chunk_weights=([1.0] * 30, None))


def test_heat2d_solve_rebalanced_recuts(single_mesh):
    from repro.core.stencil import heat2d_init, heat2d_solve
    from repro.runtime.rebalance import heat2d_solve_rebalanced

    u0 = heat2d_init(32, 32)
    ref, res_ref = heat2d_solve(u0, single_mesh, ("data",), 12, "hdot", 4)

    def cost_fn(idx, shape):
        cells = int(np.prod(shape))
        return (4.0 if idx[0] == 0 else 1.0) * cells * 1e-6

    u, res, info = heat2d_solve_rebalanced(
        u0, single_mesh, ("data",), 12, "hdot", 4, rebalance_every=4,
        chunk_cost_fn=cost_fn)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(res), np.asarray(res_ref),
                               rtol=1e-6, atol=1e-6)
    assert info["recompiles"] >= 1
    first, last = info["cut_history"][0][0], info["cut_history"][-1][0]
    assert last[0] < first[0]  # the slow chunk shrank

    # no per-chunk signal -> the cut must stay put
    u2, _, info2 = heat2d_solve_rebalanced(
        u0, single_mesh, ("data",), 12, "hdot", 4, rebalance_every=4)
    assert info2["recompiles"] == 0
    np.testing.assert_array_equal(np.asarray(u2), np.asarray(ref))

    with pytest.raises(ValueError, match="rebalance_every"):
        heat2d_solve_rebalanced(u0, single_mesh, ("data",), 4,
                                rebalance_every=-1)


# -------------------------------------------------- reassignment edge cases
def test_reassign_host_shards_duplicates_dedupe():
    assert reassign_host_shards(4, [1, 1, 1]) == reassign_host_shards(4, [1])


def test_reassign_host_shards_range_edges():
    with pytest.raises(ValueError):
        reassign_host_shards(0, [])
    with pytest.raises(ValueError):
        reassign_host_shards(4, [-1])
    with pytest.raises(ValueError):
        reassign_host_shards(4, [4])
    with pytest.raises(RuntimeError):
        reassign_host_shards(3, [0, 1, 2])
    assert reassign_host_shards(1, []) == {0: [0]}
    # every lost slice lands on exactly one survivor, none dropped
    out = reassign_host_shards(5, [0, 2])
    served = sorted(s for v in out.values() for s in v)
    assert served == [0, 1, 2, 3, 4]
    assert set(out) == {1, 3, 4}
