"""Halo exchange with interior/boundary overlap (paper §3.2, Figure 3).

Two schedules over the same decomposition:

- ``two_phase``  — the paper's MPI+OpenMP baseline: exchange ALL halos, then
  compute the whole block. The compute depends on every halo, so communication
  serializes with computation (fork-join / "two-phase programming").

- ``hdot``       — the paper's technique: the local block is over-decomposed
  into interior + boundary subdomains. Boundary strips are the only consumers
  of the halo ppermutes, so the (much larger) interior compute is independent
  of communication and XLA's async latency-hiding scheduler overlaps them —
  the SPMD analogue of OmpSs-2 tasks with fine-grained `inout(subdomain)`
  dependencies plus TAMPI-style asynchronous communication.

The hdot schedule over-decomposes the interior into ``subdomains`` chunk
tasks, each reading ONLY its slice of the source (plus `width` ghost rows), so
boundary strips are computed exactly once and the scheduler sees several
independent interior tasks to hide the exchange behind.

For multi-step solvers, :func:`halo_scan_nd` is a double-buffered driver: the
halos for step k+1 ride a ppermute issued as soon as step k's boundary strips
are done — i.e. the exchange for the NEXT step is in flight while the CURRENT
step's interior chunks compute, removing the per-step comm/compute dependency
chain entirely (one pipeline-fill exchange at the start is the only exposed
latency; the drain step is peeled, so no dead final exchange is issued).

The machinery is N-DIMENSIONAL: ``axes`` is a tuple of ``(axis_name, dim)``
pairs — one per decomposed array dim — and the same scheme recurses over any
number of mesh axes (paper §3: ONE partition function, applied at process
level and again at task level, at every depth of the hierarchy):

  * :func:`exchange_halo_nd` moves each axis's face slab (one ppermute pair
    per axis, corner-free — star stencils only),
  * :func:`stencil_with_halo_nd` splits the block into 2·N boundary-face
    tasks plus an N-D interior chunk grid cut by the SAME ``decompose_grid``
    scheme used at process level (via :func:`repro.core.domain.interior_boxes`),
  * :func:`halo_scan_nd` double-buffers ALL axes' exchanges behind the
    interior compute, stitching each axis's outgoing edges from the face
    outputs alone so every ppermute departs before any interior chunk runs.

The N-D family additionally takes ``weights=`` — per-dim explicit chunk
extents (the canonical cuts from :func:`repro.core.domain.interior_cuts`) —
so a measured-cost re-partition produces UNEVEN interior chunk grids while
the onion face partition (and thus the ppermute schedule) is untouched: the
faces depend only on `width` and the block's tiles, never on where the
interior is cut.

All functions run inside ``shard_map`` bodies; `axis_name` names the mesh axis
that carries the process-level domain decomposition for `dim`.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.domain import face_boxes, interior_boxes, tile_span

# One decomposed dim: (mesh_axis_name, array_dim).
Axes = Sequence[Tuple[str, int]]

# The HDOT stages, as `jax.named_scope` names: every op of a solver is traced
# under at most one of them (the innermost wins), so a device trace's op
# names (`tf_op`) say which stage spent the time. The solvers in
# core/stencil.py and the reductions in core/reduction.py use the same set.
FACES = "hdot.faces"          # boundary-face sources and their stencils
INTERIOR = "hdot.interior"    # interior chunk stencils (two-phase: the block)
ASSEMBLE = "hdot.assemble"    # concatenates of chunk and face outputs
EXCHANGE = "hdot.exchange"    # halo slices, ppermutes, pads and stitching
REDUCE = "hdot.reduce"        # residuals, dot products, their allreduces
UPDATE = "hdot.update"        # the solver's vector updates and scalars

def _edge(u: jax.Array, dim: int, side: str, width: int) -> jax.Array:
    n = u.shape[dim]
    if side == "lo":
        return lax.slice_in_dim(u, 0, width, axis=dim)
    return lax.slice_in_dim(u, n - width, n, axis=dim)


def exchange_edges(lo_edge: jax.Array, hi_edge: jax.Array, axis_name: str,
                   periodic: bool = False) -> Tuple[jax.Array, jax.Array]:
    """ppermute pre-sliced edge strips; returns (lo_halo, hi_halo).

    The lo halo is the PREVIOUS rank's hi edge (sent "forward"), the hi halo
    the NEXT rank's lo edge (sent "backward"). Taking the edges as arguments
    (instead of slicing internally) lets pipelined callers hand over freshly
    computed boundary strips, so the ppermute depends only on those strips —
    not on the assembled block — and can launch while interior tasks run.

    Non-periodic edge shards receive zeros (ppermute semantics), matching the
    paper's `isBoundary` gating — the zero halo is masked out by callers that
    use boundary conditions.
    """
    n = lax.axis_size(axis_name)
    with jax.named_scope(EXCHANGE):
        if n == 1:
            if periodic:  # wrap around to own edges
                return hi_edge, lo_edge
            return jnp.zeros_like(hi_edge), jnp.zeros_like(lo_edge)
        if periodic:
            fwd = [(i, (i + 1) % n) for i in range(n)]
            bwd = [(i, (i - 1) % n) for i in range(n)]
        else:
            fwd = [(i, i + 1) for i in range(n - 1)]
            bwd = [(i, i - 1) for i in range(1, n)]
        lo_halo = lax.ppermute(hi_edge, axis_name, fwd)
        hi_halo = lax.ppermute(lo_edge, axis_name, bwd)
        return lo_halo, hi_halo


def exchange_halo(u: jax.Array, axis_name: str, width: int, dim: int,
                  periodic: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Returns (lo_halo, hi_halo): the neighbor edges this shard receives."""
    with jax.named_scope(EXCHANGE):
        return exchange_edges(_edge(u, dim, "lo", width),
                              _edge(u, dim, "hi", width), axis_name, periodic)


def pad_with_halo(u: jax.Array, axis_name: str, width: int, dim: int,
                  periodic: bool = False) -> jax.Array:
    """Two-phase building block: concat [lo_halo, u, hi_halo] along `dim`."""
    with jax.named_scope(EXCHANGE):
        lo, hi = exchange_halo(u, axis_name, width, dim, periodic)
        return jnp.concatenate([lo, u, hi], axis=dim)


# --------------------------------------------------------------------------
# N-D core — corner-free multi-axis pipelining.
#
# `stencil_fn(padded)` consumes a block padded by `width` ghost cells on BOTH
# ends of EVERY dim in `dims` and must return the updated un-padded block.
# "Star"-shaped stencils only: corners between two decomposed dims are never
# exchanged (sufficient for the paper's Heat2D 5-point and CREAMS
# per-direction WENO5 flux tasks; HPCCG's 27-point corner couplings ride the
# sequential face-message chain in core/stencil.py instead).
#
# Partition of a block with extents (n_0 .. n_{N-1}) along the decomposed
# dims ("onion" faces — the 2-D strips generalized):
#   face (k, lo/hi) owns  dims j<k: the interior range [w, n_j - w)
#                         dim  k  : [0, w)  /  [n_k - w, n_k)
#                         dims j>k: the full extent [0, n_j)
#   interior: [w, n_j - w) on every decomposed dim, cut into a grid of chunk
#   tasks by `interior_boxes` — the process-level partition scheme reused at
#   task level, per the paper.
# halo_scan_nd widens a face's band from w to a tile (`_tile_quanta`) and
# snaps the chunk cuts to tiles, so every task writes whole tiles: the
# interior is then [lo_j, hi_j) of `tile_span`, and w stays the halo.
# Face (k, ·) consumes ONLY axis k's halo plus restricted slices of the
# later axes' halos (zero in the corner ghosts, which star stencils never
# read), so each halo ppermute pair has exactly two consumer tasks.
# --------------------------------------------------------------------------

def _sl(u: jax.Array, dim: int, a: int, b: int) -> jax.Array:
    return lax.slice_in_dim(u, a, b, axis=dim)


def _norm_subn(subdomains, n: int) -> Tuple[int, ...]:
    """Grainsize knob: an int means the same chunk count on every dim."""
    if isinstance(subdomains, int):
        return (subdomains,) * n
    t = tuple(subdomains)
    if len(t) != n:
        raise ValueError(
            f"subdomains={subdomains!r} has {len(t)} entries but the "
            f"decomposition is {n}-dimensional; pass an int or one chunk "
            f"count per dim")
    return t


def exchange_halo_nd(u: jax.Array, axes: Axes, width: int,
                     periodic: bool = False
                     ) -> List[Tuple[jax.Array, jax.Array]]:
    """One ppermute pair per decomposed axis; returns [(lo_k, hi_k), ...] in
    `axes` order. Corner ghosts are NOT exchanged."""
    with jax.named_scope(EXCHANGE):
        return [exchange_halo(u, a, width, d, periodic) for a, d in axes]


def pad_with_halo_nd(u: jax.Array, halos, width: int,
                     dims: Sequence[int]) -> jax.Array:
    """Assemble the corner-free padded block: face halos on every decomposed
    dim, ZEROS in the corner ghosts (star stencils never read them)."""
    out = u
    with jax.named_scope(EXCHANGE):
        for k in reversed(range(len(dims))):
            lo, hi = halos[k]
            pads = [(0, 0)] * u.ndim
            for j in range(k + 1, len(dims)):
                pads[dims[j]] = (width, width)
            lo = jnp.pad(lo, pads)
            hi = jnp.pad(hi, pads)
            out = jnp.concatenate([lo, out, hi], axis=dims[k])
    return out


def _face_src_nd(u: jax.Array, halos, k: int, side: str, width: int,
                 dims: Sequence[int], spans=None) -> jax.Array:
    """Ghost-extended source for face (k, side) — the ONLY consumer of axis
    k's `side` halo. Along earlier dims the face outputs the interior range,
    so u's own cells are the ghosts (no halo needed); along later dims the
    face spans the full extent, so their halos are stitched in, restricted
    to this face's cells and zero-padded into the corners. `spans` gives
    each decomposed dim's interior [lo, hi) (default [w, n - w)): the face
    owns the band outside it, which may be thicker than the halo."""
    w = width
    dk = dims[k]
    nk = u.shape[dk]
    spans = spans or [(w, u.shape[d] - w) for d in dims]
    halos = list(halos)
    for j in range(k):  # restrict u and the halos to the earlier interiors
        a, b = spans[j][0] - w, spans[j][1] + w
        if (a, b) != (0, u.shape[dims[j]]):
            u = _sl(u, dims[j], a, b)
            halos = [h if i < k else tuple(_sl(x, dims[j], a, b) for x in h)
                     for i, h in enumerate(halos)]
    lo_k, hi_k = halos[k]
    if side == "lo":
        cells = (0, spans[k][0] + w)  # the u-cells this face's stencil reads
        src = jnp.concatenate([lo_k, _sl(u, dk, *cells)], axis=dk)
        zk = (w, 0)                 # where axis k's halo sits inside src
    else:
        cells = (spans[k][1] - w, nk)
        src = jnp.concatenate([_sl(u, dk, *cells), hi_k], axis=dk)
        zk = (0, w)
    for j in range(k + 1, len(dims)):
        lo_j, hi_j = halos[j]

        def clip(h):
            h = _sl(h, dk, *cells)
            pads = [(0, 0)] * u.ndim
            pads[dk] = zk                       # corner with axis k: zeros
            for jp in range(k + 1, j):
                pads[dims[jp]] = (width, width)  # corner with axis jp: zeros
            return jnp.pad(h, pads)

        src = jnp.concatenate([clip(lo_j), src, clip(hi_j)], axis=dims[j])
    return src


def _faces_nd(u: jax.Array, halos,
              stencil_fn: Callable[[jax.Array], jax.Array], width: int,
              dims: Sequence[int]) -> List[Tuple[jax.Array, jax.Array]]:
    """The 2·N boundary-face tasks — the only consumers of the halos."""
    with jax.named_scope(FACES):
        return [(stencil_fn(_face_src_nd(u, halos, k, "lo", width, dims)),
                 stencil_fn(_face_src_nd(u, halos, k, "hi", width, dims)))
                for k in range(len(dims))]


def _chunk_grid_nd(ext: Sequence[int], width: int,
                   subdomains: Tuple[int, ...], weights) -> Tuple[list, list]:
    """Resolve the interior chunk grid: per-dim chunk counts (`subdomains`
    clamped so uniform chunks stay >= 2*width) plus the optional measured-cost
    cut. `weights` is None or one entry per dim — None (uniform) or the
    explicit chunk extents from :func:`repro.core.domain.interior_cuts`; an
    extents entry fixes that dim's chunk count and must sum to the interior
    extent."""
    w = width
    ks = [max(1, min(k, (n - 2 * w) // max(1, 2 * w)))  # keep chunks >= 2w
          for k, n in zip(subdomains, ext)]
    if weights is None:
        return ks, None
    wts = list(weights)
    if len(wts) != len(ext):
        raise ValueError(
            f"weights names {len(wts)} dims but the decomposition is "
            f"{len(ext)}-dimensional — one entry (or None) per dim required")
    for lvl, entry in enumerate(wts):
        if entry is None:
            continue
        entry = tuple(int(v) for v in entry)
        inner = max(0, ext[lvl] - 2 * w)
        if sum(entry) != inner or any(v < 0 for v in entry):
            raise ValueError(
                f"weights[{lvl}]={entry} must be non-negative chunk extents "
                f"summing to the interior extent {inner} (use "
                f"repro.core.domain.interior_cuts to canonicalize measured "
                f"costs)")
        wts[lvl] = entry
        ks[lvl] = len(entry)  # an explicit cut fixes the chunk count
    return ks, wts


def _box_src(u: jax.Array, box, width: int, dims: Sequence[int]) -> jax.Array:
    """An interior box's cells plus `width` ghosts along each decomposed
    dim: what a chunk task's stencil reads."""
    for lvl, d in enumerate(dims):
        u = _sl(u, d, box.start[lvl] - width, box.stop[lvl] + width)
    return u


def _task_cut(u, dims: Sequence[int], width: int,
              subdomains: Tuple[int, ...], weights=None):
    """:func:`halo_scan_nd`'s task partition of a block (`u` an array or
    its shape and dtype): each decomposed dim's interior span, the onion
    face boxes and the interior chunk boxes, all along the decomposed dims,
    cut on the tiles of :func:`_tile_quanta`."""
    ext = [u.shape[d] for d in dims]
    ks, wts = _chunk_grid_nd(ext, width, subdomains, weights)
    quanta = _tile_quanta(u, dims, width, ks)
    spans = [tile_span(n, width, q) for n, q in zip(ext, quanta)]
    return (spans, face_boxes(ext, spans),
            interior_boxes(ext, width, ks, wts, quanta))


def _tile_quanta(u: jax.Array, dims: Sequence[int], width: int,
                 ks: Sequence[int]) -> List[int]:
    """Per decomposed dim, the multiple the task boxes are cut on: the
    block's tile along that dim — 128 lanes on the last array dim, 32 bytes
    of sublanes (8 float32, 16 bfloat16 rows) on the second-to-last — so
    every task writes whole tiles and its stencil can fuse into its write.
    Other dims, and a dim whose tile-aligned interior holds fewer tiles than
    chunks, take 1: the cut of [width, n - width) as it is."""
    qs = []
    for d, k in zip(dims, ks):
        q = {u.ndim - 1: 128,
             u.ndim - 2: max(1, 32 // u.dtype.itemsize)}.get(d, 1)
        lo, hi = tile_span(u.shape[d], width, q)
        qs.append(q if (hi - lo) // q >= k else 1)
    return qs


def _interior_chunks_nd(u: jax.Array,
                        stencil_fn: Callable[[jax.Array], jax.Array],
                        width: int, dims: Sequence[int],
                        subdomains: Tuple[int, ...],
                        weights=None) -> jax.Array:
    """The interior chunk tasks, concatenated back together: cells [w, n-w)
    per decomposed dim as an N-D grid of independent chunks, cut by
    `interior_boxes` — the process-level partition scheme reused at task
    level. A chunk reads only its subdomain plus `width` ghosts. Chunks are
    disjoint work the latency-hiding scheduler interleaves with every axis's
    ppermutes. `weights` (per-dim explicit chunk extents) makes the grid
    UNEVEN — the measured-cost re-cut — without touching the face
    partition."""
    with jax.named_scope(INTERIOR):
        ext = [u.shape[d] for d in dims]
        ks, wts = _chunk_grid_nd(ext, width, subdomains, weights)
        outs = [stencil_fn(_box_src(u, b, width, dims))  # row-major
                for b in interior_boxes(ext, width, ks, wts)]
        with jax.named_scope(ASSEMBLE):
            for lvl in range(len(ks) - 1, -1, -1):  # row-major -> nested concat
                k = ks[lvl]
                outs = [outs[i] if k == 1
                        else jnp.concatenate(outs[i:i + k], axis=dims[lvl])
                        for i in range(0, len(outs), k)]
    return outs[0]


def _assemble_nd(faces, interior: jax.Array,
                 dims: Sequence[int]) -> jax.Array:
    """Wrap the interior chunk grid in the face outputs, innermost dim out."""
    out = interior
    with jax.named_scope(ASSEMBLE):
        for k in reversed(range(len(dims))):
            lo, hi = faces[k]
            out = jnp.concatenate([lo, out, hi], axis=dims[k])
    return out


def stencil_with_halo_nd(u: jax.Array, halos,
                         stencil_fn: Callable[[jax.Array], jax.Array],
                         width: int, dims: Sequence[int],
                         subdomains=2, weights=None) -> jax.Array:
    """Communication-free half of the N-D hdot schedule: apply `stencil_fn`
    to a block whose 2·N face halos were ALREADY received (e.g. pipelined by
    halo_scan_nd or a solver carrying halos across iterations)."""
    dims = tuple(dims)
    subdomains = _norm_subn(subdomains, len(dims))
    if any(u.shape[d] < 4 * width for d in dims):  # degenerate: no interior
        padded = pad_with_halo_nd(u, halos, width, dims)
        with jax.named_scope(INTERIOR):
            return stencil_fn(padded)
    faces = _faces_nd(u, halos, stencil_fn, width, dims)
    interior = _interior_chunks_nd(u, stencil_fn, width, dims, subdomains,
                                   weights)
    return _assemble_nd(faces, interior, dims)


def stencil_two_phase_nd(u: jax.Array,
                         stencil_fn: Callable[[jax.Array], jax.Array],
                         axes: Axes, width: int,
                         periodic: bool = False) -> jax.Array:
    """comm(all axes); barrier; compute(whole block) — paper Code 2."""
    dims = tuple(d for _, d in axes)
    halos = exchange_halo_nd(u, axes, width, periodic)
    padded = pad_with_halo_nd(u, halos, width, dims)
    with jax.named_scope(INTERIOR):
        return stencil_fn(padded)


def stencil_hdot_nd(u: jax.Array,
                    stencil_fn: Callable[[jax.Array], jax.Array],
                    axes: Axes, width: int, periodic: bool = False,
                    subdomains=2, weights=None) -> jax.Array:
    """N-D interior/boundary over-decomposition (paper Code 4): 2·N face
    tasks consume the N ppermute pairs; the interior chunk grid depends only
    on `u`. Numerics identical to the two-phase schedule (asserted in tests).
    """
    dims = tuple(d for _, d in axes)
    if any(u.shape[d] < 4 * width for d in dims):
        return stencil_two_phase_nd(u, stencil_fn, axes, width, periodic)
    halos = exchange_halo_nd(u, axes, width, periodic)
    return stencil_with_halo_nd(u, halos, stencil_fn, width, dims, subdomains,
                                weights)


def stencil_apply_nd(u: jax.Array,
                     stencil_fn: Callable[[jax.Array], jax.Array],
                     axes: Axes, width: int, periodic: bool = False,
                     mode: str = "hdot", subdomains=2,
                     weights=None) -> jax.Array:
    if mode == "hdot":
        return stencil_hdot_nd(u, stencil_fn, axes, width, periodic,
                               subdomains, weights)
    if mode in ("none", "two_phase"):
        return stencil_two_phase_nd(u, stencil_fn, axes, width, periodic)
    raise ValueError(f"unknown overlap mode {mode!r}")


def halo_scan_nd(u: jax.Array, stencil_fn: Callable[[jax.Array], jax.Array],
                 axes: Axes, width: int, steps: int,
                 periodic: bool = False, mode: str = "hdot", subdomains=2,
                 partial_fn: Optional[Callable[[jax.Array, jax.Array],
                                               jax.Array]] = None,
                 unroll: int = 1, peel: bool = True,
                 weights=None) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Double-buffered multi-step stencil driver on an N-D process mesh.

    In hdot mode the halos for step k arrive with the loop carry, so a step
    can (1) finish its 2·N boundary faces — the only halo consumers;
    (2) IMMEDIATELY launch EVERY axis's ppermute pair for step k+1 (the new
    block's axis-k edges are stitched from the face outputs alone,
    corner-free); (3) only then chew through its interior chunk grid. All N
    exchanges are therefore always in flight behind the interior compute;
    the only exposed latency is the pipeline-fill exchange before the loop.

    Task-owned output: the loop carries two blocks and a step reads one and
    writes the other — every face and chunk task writes its cells straight
    into the spare block (`dynamic_update_slice` at its box), so no
    concatenate assembles the block. The loop runs two steps a trip (A -> B,
    B -> A), so each block keeps its slot in the carry: a loop that swapped
    them would make the compiler copy a block every trip. The spare is
    allocated once a solve; an odd step count takes one step after the
    loop.

    The final step is PEELED out of the loop (pipeline drain): the in-loop
    exchange would feed a step that never runs, so the loop covers steps-1
    steps and the last step consumes its carried halos without launching
    new ppermutes — N dead exchange pairs per solve saved. ``peel=False``
    keeps the dead exchange for the regression tests that count ppermutes:
    with an even step count the last step runs inside the loop and launches
    its exchange there; with an odd count the last step follows the loop
    and the compiler drops its unused exchange from the optimized program.

    Tile-aligned tasks: on the block's last two array dims a face owns a
    band a tile thick (128 lanes; 8 float32 or 16 bfloat16 rows) and the
    chunk cuts are snapped to tiles (:func:`repro.core.domain.tile_span`,
    ``interior_boxes(quantum=)``), so every task writes whole tiles and the
    compiler fuses its stencil into its write, in place in the spare block.
    A dim whose interior holds fewer tiles than chunks keeps the cut of
    [w, n - w). The halo stays `width` cells.

    `partial_fn(new, old)` optionally gives a per-step output the paper's
    Code 5 way: each task maps its own new cells and the old cells they
    replace to a partial (e.g. ``max |new - old|`` for a residual), taken in
    the task's own stage scope, and the step's partials are max-combined by
    :func:`repro.core.reduction.hdot_reduce` over the tasks and the
    decomposition's mesh axes. A partial re-applies `stencil_fn` to its
    task's source, read through an `optimization_barrier`: the same cells
    in the same order, so the same values, while the task's write stays the
    stencil's only consumer (a second consumer makes the compiler write the
    task's cells to a temporary and copy them into the block). That is one
    more read of the source and no write; an `f(new, old)` over both whole
    blocks after the step would read both and make the compiler copy the
    old block, which the next step overwrites.
    The stacked per-step results are returned as the second element (None
    without `partial_fn`). Numerics are identical to `steps` iterated calls
    of :func:`stencil_apply_nd` — asserted in tests. `unroll` is forwarded
    to lax.scan over the loop's trips (the HLO-inspection tests unroll fully
    so every exchange is a countable op definition). `weights` (per-dim
    explicit chunk extents of [w, n - w) from
    :func:`repro.core.domain.interior_cuts`) cuts the interior chunk grid
    unevenly, each cut snapped to the tile — the face partition and the
    ppermute schedule are unchanged, so a measured-cost re-cut never alters
    the communication shape.
    """
    axes = tuple((a, d) for a, d in axes)
    dims = tuple(d for _, d in axes)
    names = tuple(a for a, _ in axes)
    w = width
    ext = tuple(u.shape[d] for d in dims)

    def reduce_partials(parts):
        # core.reduction imports this module's stage names: import late
        from repro.core.reduction import hdot_reduce
        return hdot_reduce(parts, names, "max")

    if mode != "hdot" or any(n < 4 * w for n in ext) or steps < 1:
        # two-phase baseline (or degenerate block / empty scan, which keeps
        # the length-0 stacked-outs contract): plain comm->compute scan, the
        # whole block one task
        def body(u, _):
            u_new = stencil_apply_nd(u, stencil_fn, axes, w, periodic,
                                     mode, subdomains, weights)
            if partial_fn is None:
                return u_new, None
            with jax.named_scope(REDUCE):
                part = partial_fn(u_new, u)
            return u_new, reduce_partials([part])
        return lax.scan(body, u, None, length=steps, unroll=unroll)

    spans, fboxes, cboxes = _task_cut(u, dims, w,
                                      _norm_subn(subdomains, len(dims)),
                                      weights)
    # The tasks, faces first (k-major, lo before hi), each as its box along
    # the decomposed dims and the source its stencil reads from a block.
    sides = [(k, side) for k in range(len(dims)) for side in ("lo", "hi")]
    tasks = [(FACES, box, partial(_face_src_nd, k=k, side=side, width=w,
                                  dims=dims, spans=spans))
             for box, (k, side) in zip(fboxes, sides)]
    tasks += [(INTERIOR, box,
               lambda blk, halos, box=box: _box_src(blk, box, w, dims))
              for box in cboxes
              if box.size]  # a snapped or explicit cut may hold empty chunks
    nfaces = len(fboxes)

    def exchange_from_faces(faces):
        # The new block's axis-k edges, stitched from face outputs alone —
        # still no interior dependency, so every pair departs before any
        # interior chunk is touched. Axis k's edge spans the full extent of
        # every other dim: the earlier axes' faces contribute their first /
        # last `w` cells along dim k (faces of LATER axes never reach the
        # edge region — their dim-k extent is the interior range).
        halos_next = []
        with jax.named_scope(EXCHANGE):
            for k, (a, dk) in enumerate(axes):
                lo_e, hi_e = faces[2 * k:2 * k + 2]
                nk = ext[k]
                if spans[k] != (w, nk - w):  # a band thicker than w
                    lo_e = _edge(lo_e, dk, "lo", w)
                    hi_e = _edge(hi_e, dk, "hi", w)
                for j in reversed(range(k)):
                    lo_j, hi_j = faces[2 * j:2 * j + 2]
                    lo_e = jnp.concatenate(
                        [_sl(lo_j, dk, 0, w), lo_e, _sl(hi_j, dk, 0, w)],
                        axis=dims[j])
                    hi_e = jnp.concatenate(
                        [_sl(lo_j, dk, nk - w, nk), hi_e,
                         _sl(hi_j, dk, nk - w, nk)], axis=dims[j])
                halos_next.append(exchange_edges(lo_e, hi_e, a, periodic))
        return halos_next

    def step(src, dst, halos, exchange):
        """One step of `src`, each task writing its cells into `dst`;
        returns (dst, the next step's halos or None, the reduced partials
        or None)."""
        with jax.named_scope(FACES):
            faces = [stencil_fn(src_of(src, halos))
                     for _, _, src_of in tasks[:nfaces]]
        halos_next = exchange_from_faces(faces) if exchange else None
        with jax.named_scope(INTERIOR):
            outs = faces + [stencil_fn(src_of(src, halos))
                            for _, _, src_of in tasks[nfaces:]]
        # The partials read `src` through a barrier, so each re-derives its
        # task's stencil in a reduction of its own: the task's write stays
        # the only consumer of its stencil, and the compiler fuses the
        # stencil into that write, in place in `dst`.
        view = (lax.optimization_barrier(src) if partial_fn is not None
                else None)
        parts = []
        for (scope, box, src_of), out in zip(tasks, outs):
            at = [0] * src.ndim
            for d, s in zip(dims, box.start):
                at[d] = s
            # the task's own scope: its stencil fuses into the write and
            # the partial's reduction, whose time must read as that stage's
            with jax.named_scope(scope):
                dst = lax.dynamic_update_slice(dst, out, at)
                if partial_fn is not None:
                    old = lax.slice(view, at, [a + n for a, n in
                                               zip(at, out.shape)])
                    parts.append(partial_fn(stencil_fn(src_of(view, halos)),
                                            old))
        return dst, halos_next, (reduce_partials(parts)
                                 if partial_fn is not None else None)

    def trip(carry, _):
        a, b, halos = carry
        b, halos, r0 = step(a, b, halos, True)
        a, halos, r1 = step(b, a, halos, True)
        return (a, b, halos), (None if partial_fn is None
                               else jnp.stack([r0, r1]))

    halos = exchange_halo_nd(u, axes, w, periodic)  # pipeline fill
    a, b = u, jnp.zeros_like(u)                    # b: the spare block
    trips, odd = divmod(steps - 1 if peel else steps, 2)
    outs = []                                      # per-step results, stacked
    if trips:
        (a, b, halos), o = lax.scan(trip, (a, b, halos), None, length=trips,
                                    unroll=unroll)
        outs.append(None if o is None else o.reshape((-1,) + o.shape[2:]))
    if odd:
        b, halos, r = step(a, b, halos, True)
        a, b = b, a
        outs.append(None if r is None else r[None])
    if peel:
        # Peeled drain: the last step consumes its halos, launches nothing.
        a, _, r = step(a, b, halos, False)
        outs.append(None if r is None else r[None])
    if partial_fn is None:
        return a, None
    return a, jnp.concatenate(outs)


def multi_dim_stencil(u: jax.Array,
                      per_dim_fn: Callable[[jax.Array, int], jax.Array],
                      decomp: Sequence[Tuple[int, Optional[str]]],
                      width: int, periodic: bool = False,
                      mode: str = "hdot") -> jax.Array:
    """Apply a direction-split stencil along several decomposed dims (the
    CREAMS pattern: euler_LLF_x/y/z are separate per-direction tasks whose
    results sum). `decomp` lists (dim, mesh_axis_or_None); un-sharded dims use
    a local pad, and the task on the padded block runs as the interior. Dims
    left out of `decomp` (a leading component axis) ride along whole."""
    total = None
    for dim, axis_name in decomp:
        fn = partial(per_dim_fn, dim=dim)
        if axis_name is None:
            with jax.named_scope(EXCHANGE):
                if periodic:
                    padded = jnp.concatenate(
                        [_edge(u, dim, "hi", width), u,
                         _edge(u, dim, "lo", width)], axis=dim)
                else:
                    pads = [(0, 0)] * u.ndim
                    pads[dim] = (width, width)
                    padded = jnp.pad(u, pads)
            with jax.named_scope(INTERIOR):
                out = fn(padded)
        else:
            out = stencil_apply_nd(u, fn, ((axis_name, dim),), width,
                                   periodic, mode, (4,))
        if total is None:
            total = out
        else:
            with jax.named_scope(UPDATE):
                total = total + out
    return total
