"""The paper's applications (§4) rebuilt on the HDOT core: Heat2D, a
CREAMS-like RK3 multi-direction stencil, and HPCCG's preconditioned CG.

Each app exposes the SAME solver under the two schedules
(``mode='two_phase'`` = paper's MPI+OpenMP baseline, ``mode='hdot'``), so the
benchmarks can measure the overlap delta directly, and tests can assert the
schedules are numerically identical.

All solvers are shard_map'd over the process-level decomposition — one mesh
axis (the paper's slabs), a 2-D (rows x cols) grid mesh, or (HPCCG) a full
3-D (x, y, z) mesh — and over-decompose each shard into task-level
subdomains (``subdomains=`` — the paper's grainsize knob) for residual
reductions and boundary/interior splits.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.domain import part_extents
from repro.core.halo import (EXCHANGE, INTERIOR, REDUCE, UPDATE, _norm_subn,
                             exchange_halo, halo_scan_nd, multi_dim_stencil,
                             pad_with_halo, stencil_apply_nd,
                             stencil_with_halo_nd)
from repro.core.reduction import task_reduce

_STR_AXES_WARNED: set = set()

# Host span around a solver entry (cut computation, solver lookup, dispatch),
# on the profiler's clock: a recompile or host stall there shows by name.
SOLVE_SPAN = "hdot.solve"


def normalize_mesh_axes(mesh_axes, solver: str,
                        arities: Tuple[int, ...]) -> Tuple[str, ...]:
    """THE solver mesh-topology contract: every solver takes
    ``mesh_axes: tuple[str, ...]`` — one mesh axis name per decomposed grid
    dim, arity selecting the topology (1 = the paper's slabs, 2/3 = grid
    meshes). A bare string is accepted as a deprecated 1-axis spelling and
    coerced (with a once-per-process note); anything else out of contract
    raises a ValueError naming the solver and its accepted arities."""
    if isinstance(mesh_axes, str):
        if solver not in _STR_AXES_WARNED:
            _STR_AXES_WARNED.add(solver)
            warnings.warn(
                f"{solver}: passing mesh_axes as a bare axis name is "
                f"deprecated; pass a tuple, e.g. ({mesh_axes!r},)",
                DeprecationWarning, stacklevel=3)
        axes = (mesh_axes,)
    else:
        try:
            axes = tuple(mesh_axes)
        except TypeError:
            raise ValueError(
                f"{solver}: mesh_axes must be a tuple of mesh axis names, "
                f"got {mesh_axes!r}") from None
    if not all(isinstance(a, str) for a in axes):
        raise ValueError(
            f"{solver}: mesh_axes entries must be mesh axis names (str), "
            f"got {axes!r}")
    if len(axes) not in arities:
        want = " or ".join(str(a) for a in arities)
        raise ValueError(
            f"{solver}: mesh_axes takes {want} axis name(s), got "
            f"{len(axes)}: {axes!r}")
    if len(set(axes)) != len(axes):
        raise ValueError(f"{solver}: mesh_axes repeats an axis: {axes!r}")
    return axes


# =============================================================== Heat2D (§4.1)
def _jacobi_stencil(padded: jax.Array, dim: int = 0) -> jax.Array:
    """5-point Jacobi update. `padded` has 1 ghost row on both ends of dim 0;
    dim 1 uses Dirichlet-0 global boundaries (zero pad)."""
    assert dim == 0
    p = jnp.pad(padded, ((0, 0), (1, 1)))
    return 0.25 * (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:])


def _jacobi_stencil_2d(padded: jax.Array) -> jax.Array:
    """5-point Jacobi on a block padded by 1 ghost cell on BOTH dims (the
    2-D-mesh contract; corner ghosts are dead — the star never reads them)."""
    return 0.25 * (padded[:-2, 1:-1] + padded[2:, 1:-1]
                   + padded[1:-1, :-2] + padded[1:-1, 2:])


def _abs_change(new: jax.Array, old: jax.Array) -> jax.Array:
    """One task's residual partial (paper Code 5): its largest |new - old|.
    halo_scan_nd max-reduces the partials over the tasks and the mesh."""
    return jnp.max(jnp.abs(new - old))


@functools.lru_cache(maxsize=128)
def _heat2d_solver(mesh, axes, iters: int, mode: str, subdomains, cuts=None):
    """Cached jitted solver — (mesh, config, cut) -> compiled fn. Without
    this, every heat2d_solve call re-traced and re-compiled, so repeated
    calls (and the benchmark timing loops) measured XLA compile time instead
    of solver throughput. `cuts` is the canonical per-dim chunk-extents tuple
    from a measured-cost re-partition (None = uniform): keying the cache on
    it means a rebalance recompiles ONLY when the cut actually changes and an
    unchanged cut reuses the compiled program."""
    axes = normalize_mesh_axes(axes, "heat2d_solve", (1, 2))
    subs = _norm_subn(subdomains, len(axes))
    hs_axes = tuple((a, d) for d, a in enumerate(axes))
    stencil_fn = _jacobi_stencil_2d if len(axes) == 2 else _jacobi_stencil

    def local(u):
        return halo_scan_nd(
            u, stencil_fn, hs_axes, width=1, steps=iters, periodic=False,
            mode=mode, subdomains=subs, partial_fn=_abs_change,
            weights=cuts)

    spec = P(*axes) if len(axes) == 2 else P(axes[0], None)
    f = jax.shard_map(local, mesh=mesh, in_specs=spec,
                      out_specs=(spec, P()))
    return jax.jit(f)


def _heat2d_cuts(global_shape, mesh, axes, subdomains, chunk_weights):
    """Canonicalize per-dim measured chunk costs into the hashable cut tuple
    the jitted-solver cache keys on. Each entry of `chunk_weights` is None
    (uniform), per-cell costs over the LOCAL shard's interior extent, or
    explicit chunk extents. Returns None when the resolved cut IS the uniform
    one, so a rebalance that lands back on uniform hits the same compiled
    program as a plain solve."""
    if chunk_weights is None:
        return None
    from repro.core.domain import _is_extents

    w = 1
    subs = _norm_subn(subdomains, len(axes))
    entries = list(chunk_weights)
    if len(entries) != len(axes):
        raise ValueError(
            f"heat2d_solve: chunk_weights names {len(entries)} dims but the "
            f"decomposition is {len(axes)}-dimensional")
    out = []
    is_default = []
    for d, (name, k, entry) in enumerate(zip(axes, subs, entries)):
        n_local = global_shape[d] // mesh.shape[name]
        inner = max(0, n_local - 2 * w)
        kd = max(1, min(k, inner // (2 * w)))  # the clamped default count
        if entry is None:
            out.append(None)
            is_default.append(True)
            continue
        entry = tuple(entry)
        # len == interior extent reads as per-cell costs (uniform integer
        # costs sum to the extent and would otherwise masquerade as a grid
        # of 1-cell chunk extents); any other length must be explicit extents
        if len(entry) != inner and _is_extents(entry, len(entry), inner):
            out.append(tuple(int(v) for v in entry))
        else:
            out.append(part_extents(inner, kd, entry))
        is_default.append(out[-1] == part_extents(inner, kd, None))
    # a re-cut that lands back on the default uniform grid IS no cut:
    # collapse onto the unweighted cache entry (no recompile)
    if all(is_default):
        return None
    return tuple(out)


def heat2d_solve(u0: jax.Array, mesh, mesh_axes, iters: int,
                 mode: str = "hdot", subdomains=4,
                 chunk_weights=None) -> Tuple[jax.Array, jax.Array]:
    """Run `iters` sweeps; returns (final grid, residual history).

    u0 is the GLOBAL grid; sharding happens here — process-level
    decomposition == mesh. `mesh_axes` is the unified solver topology
    contract (one mesh axis name per decomposed grid dim):

      * ``(axis,)`` — the paper's horizontal MPI slabs (1-D, dim 0),
      * ``(rows_axis, cols_axis)`` — true 2-D block decomposition over both
        grid dims via :func:`halo_scan_nd` (corner-free pipelining).

    The sweep loop is double-buffered either way: sweep k+1's halo
    ppermute(s) depart while sweep k's interior chunk tasks compute (hdot
    mode), and the drain sweep is peeled.

    `chunk_weights` (per decomposed dim: None, per-cell measured costs over
    the local interior, or explicit chunk extents) re-cuts the interior chunk
    grid by measured cost — the dynamic load-balancing path. It is
    canonicalized to chunk extents BEFORE the solver cache, so re-measuring
    identical costs (or an unchanged cut) never recompiles."""
    with jax.profiler.TraceAnnotation(SOLVE_SPAN):
        axes = normalize_mesh_axes(mesh_axes, "heat2d_solve", (1, 2))
        if isinstance(subdomains, list):
            subdomains = tuple(subdomains)
        cuts = _heat2d_cuts(u0.shape, mesh, axes, subdomains, chunk_weights)
        return _heat2d_solver(mesh, axes, iters, mode, subdomains, cuts)(u0)


def heat2d_init(nx: int, ny: int, dtype=jnp.float32) -> jax.Array:
    """Hot square blob in the middle, Dirichlet-0 edges."""
    u = jnp.zeros((nx, ny), dtype)
    cx, cy, w = nx // 2, ny // 2, max(1, nx // 8)
    return u.at[cx - w:cx + w, cy - w:cy + w].set(1.0)


# ========================================== CREAMS-like RK3 stencil (§4.2)
# 8th-order central second-derivative coefficients (halo width 4 == CREAMS Nh).
_C8 = jnp.array([-1 / 560, 8 / 315, -1 / 5, 8 / 5, -205 / 72, 8 / 5, -1 / 5, 8 / 315, -1 / 560])
# classic Williamson low-storage RK3 coefficients
_RK3_A = (0.0, -5 / 9, -153 / 128)
_RK3_B = (1 / 3, 15 / 16, 8 / 15)


def _diff2_dir(padded: jax.Array, dim: int) -> jax.Array:
    """8th-order d2/dx_dim^2 over a block padded by 4 ghosts along `dim`."""
    n = padded.shape[dim] - 8
    out = None
    for j, c in enumerate(_C8.tolist()):
        sl = lax.slice_in_dim(padded, j, j + n, axis=dim)
        out = c * sl if out is None else out + c * sl
    return out


def rk3_rhs(v: jax.Array, axis_name, mode: str,
            nu: float = 0.05) -> jax.Array:
    """Direction-split diffusion RHS (stands in for euler_LLF_x/y/z): the three
    per-direction stencils are independent tasks (paper Figure 5). `axis_name`
    is one mesh axis (z decomposed) or a (y_axis, z_axis) pair — each
    direction's stencil only ever needs its OWN axis's halo (direction-split
    stencils have no cross-dim couplings), so a 2-D mesh needs no corner
    messages at all."""
    if isinstance(axis_name, tuple):
        ay, az = axis_name
        decomp = [(0, None), (1, ay), (2, az)]
    else:
        decomp = [(0, None), (1, None), (2, axis_name)]
    return nu * multi_dim_stencil(v, _diff2_dir, decomp, width=4,
                                  periodic=True, mode=mode)


def _rk3_rhs_with_halo(v: jax.Array, lo: jax.Array, hi: jax.Array,
                       nu: float = 0.05, subdomains: int = 4) -> jax.Array:
    """RHS with z-halos already in hand (pipelined schedule): the x/y stencils
    are multi_dim_stencil's local-pad tasks, the z stencil consumes the
    carried halos — no exchange on this stage's critical path."""
    xy = multi_dim_stencil(v, _diff2_dir, [(0, None), (1, None)], width=4,
                           periodic=True)
    z = stencil_with_halo_nd(v, [(lo, hi)], functools.partial(_diff2_dir, dim=2),
                             width=4, dims=(2,), subdomains=(subdomains,))
    return nu * (xy + z)


def _rk3_rhs_with_halo_2d(v: jax.Array, hy, hz, nu: float = 0.05,
                          subdomains: int = 4) -> jax.Array:
    """RHS with BOTH mesh axes' halos already in hand ((y, z) grid mesh):
    the x stencil is a local-pad task; the y and z stencils each consume
    their own carried halo pair — neither exchange sits on this stage's
    critical path, and the per-direction interior chunks are the independent
    work both ppermute pairs hide behind."""
    x = multi_dim_stencil(v, _diff2_dir, [(0, None)], width=4, periodic=True)
    y = stencil_with_halo_nd(v, [hy], functools.partial(_diff2_dir, dim=1),
                             width=4, dims=(1,), subdomains=(subdomains,))
    z = stencil_with_halo_nd(v, [hz], functools.partial(_diff2_dir, dim=2),
                             width=4, dims=(2,), subdomains=(subdomains,))
    return nu * (x + y + z)


def rk3_local_step(v: jax.Array, axis_name: Optional[str], dt: float,
                   mode: str) -> jax.Array:
    """One 3-stage low-storage RK step (paper Code 8's rk loop): each stage is
    data-prep -> per-direction stencils -> update -> halo comm, with the HDOT
    schedule overlapping the z-direction halo with the x/y stencil tasks."""
    s = jnp.zeros_like(v)
    for a, b in zip(_RK3_A, _RK3_B):
        rhs = rk3_rhs(v, axis_name, mode)
        s = a * s + dt * rhs
        v = v + b * s
    return v


def rk3_local_step_pipelined(v: jax.Array, lo: jax.Array, hi: jax.Array,
                             axis_name: str, dt: float,
                             subdomains: int = 4, exchange_last: bool = True
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """RK3 step with z-halos carried across stages: each stage consumes the
    halos exchanged at the END of the previous stage, and launches the next
    exchange the moment its `v` update lands — so every z ppermute flies
    behind the next stage's x/y stencils and interior z chunks (the
    double-buffered analogue of Code 8's comm task). `exchange_last=False`
    peels the drain: the solve's final stage feeds no consumer, so its
    exchange would be a dead width-4 ppermute pair."""
    s = jnp.zeros_like(v)
    n_stages = len(_RK3_A)
    for i, (a, b) in enumerate(zip(_RK3_A, _RK3_B)):
        rhs = _rk3_rhs_with_halo(v, lo, hi, subdomains=subdomains)
        s = a * s + dt * rhs
        v = v + b * s
        if exchange_last or i < n_stages - 1:
            lo, hi = exchange_halo(v, axis_name, width=4, dim=2, periodic=True)
    return v, lo, hi


def rk3_local_step_pipelined_2d(v: jax.Array, hy, hz, ay: str, az: str,
                                dt: float, subdomains: int = 4,
                                exchange_last: bool = True):
    """RK3 step on a (y, z) grid mesh with BOTH axes' halos carried across
    stages: each stage consumes the pairs exchanged at the END of the
    previous stage and launches the next y AND z exchanges the moment its
    `v` update lands — so every ppermute pair flies behind the next stage's
    x stencil and the y/z interior chunks. `exchange_last=False` peels the
    drain (the solve's final stage feeds no consumer — two dead width-4
    pairs saved per solve)."""
    s = jnp.zeros_like(v)
    n_stages = len(_RK3_A)
    for i, (a, b) in enumerate(zip(_RK3_A, _RK3_B)):
        rhs = _rk3_rhs_with_halo_2d(v, hy, hz, subdomains=subdomains)
        s = a * s + dt * rhs
        v = v + b * s
        if exchange_last or i < n_stages - 1:
            hy = exchange_halo(v, ay, width=4, dim=1, periodic=True)
            hz = exchange_halo(v, az, width=4, dim=2, periodic=True)
    return v, hy, hz


@functools.lru_cache(maxsize=128)
def _rk3_solver(mesh, axes, steps: int, dt: float, mode: str):
    axes = normalize_mesh_axes(axes, "rk3_solve", (1, 2))
    two_d = len(axes) == 2
    ay, az = axes if two_d else (None, None)
    axis_name = axes if two_d else axes[0]

    def local(v):
        if (two_d and mode == "hdot" and v.shape[1] >= 16
                and v.shape[2] >= 16 and steps > 0):
            hy = exchange_halo(v, ay, width=4, dim=1, periodic=True)
            hz = exchange_halo(v, az, width=4, dim=2, periodic=True)

            def body(carry, _):
                v, hy, hz = carry
                return rk3_local_step_pipelined_2d(v, hy, hz, ay, az, dt), None

            # drain peeled: the last step's last-stage exchanges are dead
            (v, hy, hz), _ = lax.scan(body, (v, hy, hz), None,
                                      length=steps - 1)
            v, _, _ = rk3_local_step_pipelined_2d(v, hy, hz, ay, az, dt,
                                                  exchange_last=False)
            return v

        if not two_d and mode == "hdot" and v.shape[2] >= 16 and steps > 0:
            lo, hi = exchange_halo(v, axis_name, width=4, dim=2,
                                   periodic=True)  # pipeline fill

            def body(carry, _):
                return rk3_local_step_pipelined(*carry, axis_name, dt), None

            # drain peeled: the last step's last-stage exchange is dead
            (v, lo, hi), _ = lax.scan(body, (v, lo, hi), None,
                                      length=steps - 1)
            v, _, _ = rk3_local_step_pipelined(v, lo, hi, axis_name, dt,
                                               exchange_last=False)
            return v

        def body(v, _):
            return rk3_local_step(v, axis_name, dt, mode), None
        v, _ = lax.scan(body, v, None, length=steps)
        return v

    spec = P(None, ay, az) if two_d else P(None, None, axis_name)
    f = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)
    return jax.jit(f)


def rk3_solve(v0: jax.Array, mesh, mesh_axes, steps: int, dt: float = 0.05,
              mode: str = "hdot") -> jax.Array:
    """Run `steps` RK3 steps. `mesh_axes` is the unified solver topology
    contract: ``(z_axis,)`` — the paper's z-decomposed slabs — or a
    ``(y_axis, z_axis)`` pair — true 2-D (y, z) grid-mesh decomposition with
    stage-carried halos on BOTH axes (each direction-split stencil consumes
    only its own axis's pair, so the 2-D mesh needs no corner messages)."""
    axes = normalize_mesh_axes(mesh_axes, "rk3_solve", (1, 2))
    return _rk3_solver(mesh, axes, steps, dt, mode)(v0)


# ============================================================ HPCCG CG (§4.3)
def _sum27(q: jax.Array) -> jax.Array:
    """HPCCG's 27-point operator (diag=26, off-diag=-1) on a fully padded
    (nx+2, ny+2, nz+2) block; returns the (nx, ny, nz) interior."""
    nx, ny, nz = q.shape[0] - 2, q.shape[1] - 2, q.shape[2] - 2
    acc = 0.0
    for dx in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dz in (-1, 0, 1):
                sl = q[1 + dx:nx + 1 + dx, 1 + dy:ny + 1 + dy,
                       1 + dz:nz + 1 + dz]
                if dx == dy == dz == 0:
                    acc = acc + 26.0 * sl
                else:
                    acc = acc - sl
    return acc


def _stencil27_matvec(p: jax.Array, axis_name: Optional[str], mode: str,
                      halos: Optional[Tuple[jax.Array, jax.Array]] = None,
                      subdomains: int = 4) -> jax.Array:
    """y = A p for the 27-point operator on a 3-D grid stacked along z
    (dim 2), halo width 1. Only z is decomposed, so the exchanged plane
    carries all in-plane diagonals (corner-free exchange).

    `halos=(lo, hi)` supplies pre-exchanged z-planes (the pipelined CG
    schedule: the exchange for iteration k+1's matvec departs when p_{k+1} is
    formed, and only the boundary-plane tasks here consume it)."""

    def per_z(padded: jax.Array, dim: int) -> jax.Array:
        assert dim == 2
        # pad x,y locally with zeros (global Dirichlet)
        return _sum27(jnp.pad(padded, ((1, 1), (1, 1), (0, 0))))

    fn = functools.partial(per_z, dim=2)
    if halos is not None:
        return stencil_with_halo_nd(p, [halos], fn, width=1, dims=(2,),
                                    subdomains=(subdomains,))
    if axis_name is None:
        with jax.named_scope(EXCHANGE):
            padded = jnp.pad(p, [(0, 0), (0, 0), (1, 1)])
        with jax.named_scope(INTERIOR):
            return fn(padded)
    return stencil_apply_nd(p, fn, ((axis_name, 2),), width=1,
                            periodic=False, mode=mode,
                            subdomains=(subdomains,))


def _chain_fn27(dims: Tuple[int, ...]):
    """27-point apply for a block that ALREADY carries ghosts on every dim in
    `dims` (plus width-1 padding on the last dim supplied by the caller);
    the remaining dims are padded locally with zeros (global Dirichlet)."""
    pads = tuple((0, 0) if d in dims else (1, 1) for d in range(3))

    def fn(block: jax.Array) -> jax.Array:
        if any(p != (0, 0) for p in pads):
            block = jnp.pad(block, pads)
        return _sum27(block)

    return fn


def _exchange_chain(p: jax.Array, axes: Tuple[str, ...],
                    dims: Tuple[int, ...]
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sequential face-message exchange for an N-D process mesh (the MPI
    ordered-exchange trick, chained): pad every decomposed dim but the last
    IN ORDER — each pad ships the PREVIOUSLY padded block, so its face
    messages carry the earlier dims' edge values from the diagonal ranks via
    the shared neighbors — then exchange the LAST dim's faces of the fully
    padded block. The final halo planes thus carry every (multi-)corner
    coupling of the 27-point operator with face ppermutes only: one pair per
    axis, no corner messages. Returns (p_padded, lo_last, hi_last)."""
    with jax.named_scope(EXCHANGE):
        for a, d in zip(axes[:-1], dims[:-1]):
            p = pad_with_halo(p, a, 1, dim=d)
        lo, hi = exchange_halo(p, axes[-1], 1, dim=dims[-1], periodic=False)
    return p, lo, hi


def _stencil27_matvec_chain(p: jax.Array, axes: Tuple[str, ...],
                            dims: Tuple[int, ...], mode: str,
                            halos=None, subdomains: int = 4) -> jax.Array:
    """y = A p with block decomposition over the mesh dims in `dims` ((y, z)
    or (x, y, z)). `halos` is the :func:`_exchange_chain` triple,
    pre-exchanged by the pipelined CG; the interior chunk tasks along the
    last dim read only the pre-padded block, so just the boundary-plane
    tasks wait on the final ppermute pair."""
    if halos is None:
        halos = _exchange_chain(p, axes, dims)
    p1, lo, hi = halos
    fn = _chain_fn27(dims)
    if mode == "hdot":
        return stencil_with_halo_nd(p1, [(lo, hi)], fn, width=1,
                                    dims=(dims[-1],),
                                    subdomains=(subdomains,))
    with jax.named_scope(EXCHANGE):
        padded = jnp.concatenate([lo, p1, hi], axis=dims[-1])
    with jax.named_scope(INTERIOR):
        return fn(padded)


def _ddot(a: jax.Array, b: jax.Array, axis_name: Optional[str],
          subdomains: int = 4) -> jax.Array:
    """paper Code 11: per-subdomain reduction(+) partials, then allreduce."""
    with jax.named_scope(REDUCE):
        prod = (a * b).reshape(-1)
        chunks = jnp.array_split(prod, subdomains)
        partials = [jnp.sum(c, dtype=jnp.float64 if a.dtype == jnp.float64
                            else jnp.float32)
                    for c in chunks]
        local = task_reduce(partials, "sum")
        if axis_name is None:
            return local
        return lax.psum(local, axis_name)


@functools.lru_cache(maxsize=128)
def _hpccg_solver(mesh, mesh_axes, iters: int, mode: str, subdomains: int):
    axes = normalize_mesh_axes(mesh_axes, "hpccg_solve", (1, 2, 3))
    chained = len(axes) >= 2
    # the reduction axes / 1-D exchange axis, in the historical spelling
    # (bare name for slabs, tuple for chained meshes)
    axis_name = axes if chained else axes[0]
    if chained:
        # trailing grid dims carry the mesh: (y, z) for a pair, (x, y, z)
        # for a full 3-D mesh
        cdims = tuple(range(3 - len(axes), 3))

    def matvec(p, halos):
        if chained:
            return _stencil27_matvec_chain(p, axes, cdims, mode, halos=halos,
                                           subdomains=subdomains)
        return _stencil27_matvec(p, axis_name, mode, halos=halos,
                                 subdomains=subdomains)

    def next_halos(p):
        if chained:
            return _exchange_chain(p, axes, cdims)
        return exchange_halo(p, axis_name, width=1, dim=2, periodic=False)

    def local(b_loc):
        x = jnp.zeros_like(b_loc)
        r = b_loc
        p = r
        rtrans = _ddot(r, r, axis_name, subdomains)
        pipelined = mode == "hdot" and b_loc.shape[2] >= 4 and iters > 0

        def step(x, r, p, rtrans, halos):
            Ap = matvec(p, halos)
            pAp = _ddot(p, Ap, axis_name, subdomains)
            with jax.named_scope(UPDATE):
                alpha = rtrans / pAp
                x = x + alpha * p          # waxpby tasks
                r = r - alpha * Ap
            rtrans_new = _ddot(r, r, axis_name, subdomains)
            with jax.named_scope(UPDATE):
                beta = rtrans_new / rtrans
                p = r + beta * p
            return x, r, p, rtrans_new

        if pipelined:
            def body(carry, _):
                x, r, p, rtrans, halos = carry
                x, r, p, rtrans = step(x, r, p, rtrans, halos)
                halos = next_halos(p)  # for the NEXT matvec
                return (x, r, p, rtrans, halos), jnp.sqrt(rtrans)

            # drain peeled: the last iteration consumes its halos but feeds
            # no further matvec — same dead-exchange saving as halo_scan
            (x, r, p, rtrans, halos), hist = lax.scan(
                (body), (x, r, p, rtrans, next_halos(p)), None,
                length=iters - 1)
            x, r, p, rtrans = step(x, r, p, rtrans, halos)
            hist = jnp.concatenate([hist, jnp.sqrt(rtrans)[None]])
            return x, hist

        def body(carry, _):
            x, r, p, rtrans = carry
            x, r, p, rtrans = step(x, r, p, rtrans, None)
            return (x, r, p, rtrans), jnp.sqrt(rtrans)

        (x, r, p, rtrans), hist = lax.scan(body, (x, r, p, rtrans), None, length=iters)
        return x, hist

    if chained:
        spec = P(*((None,) * (3 - len(axes)) + axes))
    else:
        spec = P(None, None, axis_name)
    f = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=(spec, P()))
    return jax.jit(f)


def hpccg_solve(b: jax.Array, mesh, mesh_axes, iters: int,
                mode: str = "hdot", subdomains: int = 4) -> Tuple[jax.Array, jax.Array]:
    """Unpreconditioned CG on the 27-point system (HPCCG's CG core; the paper
    taskifies ddot/waxpby/sparsemv — here each is an over-decomposed op).
    Returns (x, residual-norm history).

    `mesh_axes` is the unified solver topology contract: ``(z_axis,)``
    (z-stacked slabs), a ``(y_axis, z_axis)`` pair, or an
    ``(x_axis, y_axis, z_axis)`` triple — HPCCG's native full 3-D mesh.
    Multi-axis topologies use the sequential face-message chain
    (:func:`_exchange_chain`): each earlier dim is padded in order on the
    already-padded block, so the last dim's halo planes carry every corner
    coupling of the 27-point operator with one face ppermute pair per axis.

    hdot mode pipelines the matvec halo: the exchange(s) for iteration k+1
    are launched the moment p_{k+1} is formed, so they ride behind the two
    ddot allreduces, the waxpby tasks, and the next matvec's interior chunks
    — only the boundary-plane tasks of the next matvec wait on them. The
    jitted solver is cached per (mesh, topology, iters, mode, subdomains) so
    repeated solves (and benchmark timings) pay compile once."""
    with jax.profiler.TraceAnnotation(SOLVE_SPAN):
        axes = normalize_mesh_axes(mesh_axes, "hpccg_solve", (1, 2, 3))
        return _hpccg_solver(mesh, axes, iters, mode, subdomains)(b)
