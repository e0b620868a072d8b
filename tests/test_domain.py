"""Property tests for the hierarchical domain over-decomposition (paper §3.2):
the single partition scheme must tile exactly at every level, and the
boundary/halo accounting must match the paper's published Table 1."""
from __future__ import annotations

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.domain import (Domain, decompose_grid, halo_cells,
                               halo_fraction)

dims = st.integers(min_value=1, max_value=64)
parts = st.integers(min_value=1, max_value=8)


@given(shape=st.tuples(dims, dims), grid=st.tuples(parts, parts))
@settings(max_examples=200, deadline=None)
def test_decompose_exact_tiling(shape, grid):
    """Every cell belongs to exactly one box (disjoint + complete)."""
    boxes = decompose_grid(shape, grid)
    assert len(boxes) == grid[0] * grid[1]
    cover = np.zeros(shape, np.int32)
    for b in boxes:
        cover[b.slices()] += 1
    assert (cover == 1).all()


@given(shape=st.tuples(dims, dims), grid=st.tuples(parts, parts))
@settings(max_examples=100, deadline=None)
def test_balanced_split(shape, grid):
    """Block sizes differ by at most one cell per dimension."""
    boxes = decompose_grid(shape, grid)
    for d in range(2):
        sizes = sorted({b.shape[d] for b in boxes})
        assert sizes[-1] - sizes[0] <= 1


@given(shape=st.tuples(st.integers(8, 64), st.integers(8, 64)),
       pgrid=st.tuples(st.integers(1, 4), st.integers(1, 4)),
       sgrid=st.tuples(st.integers(1, 4), st.integers(1, 4)))
@settings(max_examples=100, deadline=None)
def test_hierarchical_reuse(shape, pgrid, sgrid):
    """Process-level boxes, over-decomposed with the SAME scheme, tile the
    global space exactly (the paper's central claim: one scheme, two levels)."""
    cover = np.zeros(shape, np.int32)
    for dom in Domain.all_ranks(shape, pgrid):
        for sub in dom.over_decompose(sgrid):
            assert dom.box.contains(sub.box)
            cover[sub.box.slices()] += 1
    assert (cover == 1).all()


@given(shape=st.tuples(st.integers(8, 32), st.integers(8, 32)),
       pgrid=st.tuples(st.integers(2, 4), st.integers(2, 4)))
@settings(max_examples=50, deadline=None)
def test_boundary_subdomains(shape, pgrid):
    """A subdomain is boundary iff it touches its domain's edge; the count of
    boundary subdomains in a kxk over-decomposition is the ring k^2-(k-2)^2."""
    dom = Domain.for_rank(shape, pgrid, 0)
    for k in (1, 2, 3):
        if min(dom.box.shape) < k:  # degenerate: empty strips touch the edge
            continue
        subs = dom.over_decompose((k, k))
        n_boundary = sum(1 for s in subs if s.is_boundary())
        assert n_boundary == k * k - max(0, k - 2) ** 2


def test_neighbors_symmetry():
    doms = Domain.all_ranks((16, 16), (4, 4))
    idx = {d.rank_index: d for d in doms}
    for d in doms:
        for (dim, side), nb in d.neighbors().items():
            back = idx[nb].neighbors()[(dim, "lo" if side == "hi" else "hi")]
            assert back == d.rank_index


def test_paper_table1_exact():
    paper = {2: 1.6, 4: 4.7, 8: 10.9, 16: 23.4, 32: 48.4}
    for ranks, pct in paper.items():
        _, _, frac = halo_fraction((128, 128), (ranks, 1), width=1)
        assert round(100 * frac, 1) == pct


@given(width=st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_halo_cells_interior_vs_edge(width):
    """Interior boxes allocate two slabs per decomposed dim, edges one."""
    doms = Domain.all_ranks((64, 64), (4, 1))
    for d in doms:
        expected = width * 64 * (1 if d.rank_index[0] in (0, 3) else 2)
        # dim-1 has no neighbors (undecomposed): restrict accounting to dim 0
        assert halo_cells(d.box, d.global_shape, width, dims=[0]) == expected
