"""The paper's applications (§4) rebuilt on the HDOT core: Heat2D, CREAMS as
a compressible Euler solver (LLF-split WENO5 fluxes per direction, RK3 in
time), and HPCCG's preconditioned CG.

Each app exposes the SAME solver under the two schedules
(``mode='two_phase'`` = paper's MPI+OpenMP baseline, ``mode='hdot'``), so the
benchmarks can measure the overlap delta directly, and tests can assert the
schedules are numerically identical.

All solvers are shard_map'd over the process-level decomposition — one mesh
axis (the paper's slabs), a 2-D (rows x cols) grid mesh, or (HPCCG) a full
3-D (x, y, z) mesh. Each solver has one decomposition path: a slab is the
grid path with one axis. Each shard is over-decomposed into task-level
subdomains: Heat2D's interior chunk grid (``subdomains=``), CREAMS's
per-direction chunks (``EULER_SUBDOMAINS``) and HPCCG's dot-product
partials (``subdomains=``).
"""
from __future__ import annotations

import functools
import math
import operator
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.domain import part_extents
from repro.core.halo import (ASSEMBLE, EXCHANGE, FACES, INTERIOR, REDUCE,
                             UPDATE, _norm_subn, exchange_halo,
                             exchange_halo_nd, halo_scan_nd,
                             multi_dim_stencil, pad_with_halo,
                             stencil_with_halo_nd)
from repro.core.reduction import hdot_reduce, task_reduce

# Host span around a solver entry (cut computation, solver lookup, dispatch),
# on the profiler's clock: a recompile or host stall there shows by name.
SOLVE_SPAN = "hdot.solve"


def normalize_mesh_axes(mesh_axes, solver: str,
                        arities: Tuple[int, ...]) -> Tuple[str, ...]:
    """THE solver mesh-topology contract: every solver takes
    ``mesh_axes: tuple[str, ...]`` — one mesh axis name per decomposed grid
    dim, arity selecting the topology (1 = the paper's slabs, 2/3 = grid
    meshes). Anything out of contract, a bare string included, raises a
    ValueError naming the solver and its accepted arities."""
    if isinstance(mesh_axes, str) or not hasattr(mesh_axes, "__iter__"):
        raise ValueError(
            f"{solver}: mesh_axes must be a tuple of mesh axis names, "
            f"got {mesh_axes!r}")
    axes = tuple(mesh_axes)
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
def _jacobi_stencil_2d(padded: jax.Array) -> jax.Array:
    """5-point Jacobi on a block padded by 1 ghost cell on BOTH dims (corner
    ghosts are dead — the star never reads them). Slabs zero-pad dim 1
    first: its Dirichlet-0 edges."""
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

    # slabs: dim 1 is whole, its Dirichlet-0 ghosts a local zero pad
    stencil_fn = (_jacobi_stencil_2d if len(axes) == 2 else
                  lambda q: _jacobi_stencil_2d(jnp.pad(q, ((0, 0), (1, 1)))))

    def local(u):
        return halo_scan_nd(
            u, stencil_fn, hs_axes, width=1, steps=iters, periodic=False,
            mode=mode, subdomains=subs, partial_fn=_abs_change,
            weights=cuts)

    spec = P(*axes)
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


# ============================== CREAMS: compressible Euler, RK3 (§4.2)
# The state is U = (rho, rho u, rho v, rho w, E) on a periodic box, shape
# (5, nx, ny, nz): the component axis leads, array dims 1, 2, 3 are x, y, z.
# Each direction's flux divergence is one task (the paper's euler_LLF_x/y/z):
# local Lax-Friedrichs flux splitting with fifth-order WENO-JS reconstruction
# (Jiang & Shu, J. Comput. Phys. 126, 1996; Shu, ICASE Report 97-65), whose
# stencil reaches 3 cells past a cell on each side: the halo width.
EULER_WIDTH = 3
# Task-level subdomains: interior chunks per decomposed direction, and the
# CFL max's partials over z.
EULER_SUBDOMAINS = 4
_WENO_EPS = 1e-6
GAMMA = 1.4           # ratio of specific heats: one calorically perfect gas
CFL = 0.5             # dt = CFL / max over cells of sum_d (|u_d| + c) / dx_d
BOX = 2 * math.pi     # the periodic box [0, BOX)^3
# classic Williamson low-storage RK3 coefficients
_RK3_A = (0.0, -5 / 9, -153 / 128)
_RK3_B = (1 / 3, 15 / 16, 8 / 15)


def _primitives(u: jax.Array):
    """Velocity (3, ...), pressure and sound speed of the conserved `u`."""
    inv_rho = 1.0 / u[0]
    vel = u[1:4] * inv_rho
    p = (GAMMA - 1.0) * (u[4] - 0.5 * u[0] * jnp.sum(vel * vel, axis=0))
    return vel, p, jnp.sqrt(GAMMA * p * inv_rho)


def _weno5(a, b, c, d, e):
    """WENO5-JS value at the face between `c` and `d` from the five cell
    values a..e, biased to the left: linear weights 1/10, 6/10, 3/10,
    Jiang-Shu smoothness indicators, epsilon 1e-6, power 2."""
    q0 = (2 * a - 7 * b + 11 * c) / 6
    q1 = (-b + 5 * c + 2 * d) / 6
    q2 = (2 * c + 5 * d - e) / 6
    s0 = 13 / 12 * (a - 2 * b + c) ** 2 + 0.25 * (a - 4 * b + 3 * c) ** 2
    s1 = 13 / 12 * (b - 2 * c + d) ** 2 + 0.25 * (b - d) ** 2
    s2 = 13 / 12 * (c - 2 * d + e) ** 2 + 0.25 * (3 * c - 4 * d + e) ** 2
    w0 = 0.1 / (_WENO_EPS + s0) ** 2
    w1 = 0.6 / (_WENO_EPS + s1) ** 2
    w2 = 0.3 / (_WENO_EPS + s2) ** 2
    return (w0 * q0 + w1 * q1 + w2 * q2) / (w0 + w1 + w2)


def euler_llf(padded: jax.Array, dim: int,
              inv_dx: Tuple[float, ...]) -> jax.Array:
    """The paper's euler_LLF_d task: -dF_d/dx_d on the cells of a state block
    padded by EULER_WIDTH ghosts at both ends of array dim `dim` (1, 2, 3 =
    x, y, z); `inv_dx` holds 1/dx of each direction.

    At each face i+1/2 the splitting speed alpha is the largest |u_d| + c of
    the six cells i-2..i+3 of the face's stencil; F+- = (F +- alpha U) / 2
    are formed on those cells, F+ reconstructed by WENO5-JS from the left
    (cells i-2..i+2) and F- mirrored from the right (cells i-1..i+3). The
    five components travel together, so the task reads the whole block."""
    w = EULER_WIDTH
    n = padded.shape[dim] - 2 * w
    vel, p, c = _primitives(padded)
    ud = vel[dim - 1]
    mom = [padded[1 + j] * ud for j in range(3)]
    mom[dim - 1] = mom[dim - 1] + p
    flux = jnp.stack([padded[dim], *mom, (padded[4] + p) * ud])
    speed = (jnp.abs(ud) + c)[None]

    def at(x, k):
        # x on cell i - 2 + k, for each of the n + 1 faces i + 1/2
        return lax.slice_in_dim(x, k, k + n + 1, axis=dim)

    alpha = functools.reduce(jnp.maximum, [at(speed, k) for k in range(2 * w)])
    plus = [0.5 * (at(flux, k) + alpha * at(padded, k)) for k in range(2 * w - 1)]
    minus = [0.5 * (at(flux, k) - alpha * at(padded, k)) for k in range(1, 2 * w)]
    face = _weno5(*plus) + _weno5(*minus[::-1])
    return (lax.slice_in_dim(face, 0, n, axis=dim)
            - lax.slice_in_dim(face, 1, n + 1, axis=dim)) * inv_dx[dim - 1]


def _cfl_dt(u: jax.Array, inv_dx, axes) -> jax.Array:
    """dt = CFL / max over cells of sum_d (|u_d| + c) / dx_d, from the state
    at a step's start: each task's partial max over its slab of z, then the
    max over the tasks and the mesh (paper Code 5). No stage of the step can
    update before it."""
    with jax.named_scope(REDUCE):
        parts = []
        for blk in jnp.array_split(u, min(EULER_SUBDOMAINS, u.shape[3]),
                                   axis=3):
            vel, _, c = _primitives(blk)
            rate = (jnp.abs(vel[0]) + c) * inv_dx[0]
            for d in (1, 2):
                rate = rate + (jnp.abs(vel[d]) + c) * inv_dx[d]
            parts.append(jnp.max(rate))
        return CFL / hdot_reduce(parts, axes, "max")


def _llf_task(inv_dx, dim=None):
    fn = functools.partial(euler_llf, inv_dx=inv_dx)
    return fn if dim is None else functools.partial(fn, dim=dim)


def _rk3_stage(u, s, rhs, dt, a: float, b: float):
    """The low-storage stage update: S = a S + dt rhs; U = U + b S."""
    with jax.named_scope(UPDATE):
        s = dt * rhs if s is None else a * s + dt * rhs
        return u + b * s, s


def _directions(axes: Tuple[str, ...]) -> Tuple[Tuple[str, int], ...]:
    """The decomposed directions: the mesh axes carry the trailing array
    dims, z for one axis, (y, z) for two, as ``(axis, dim)`` pairs."""
    return tuple(zip(axes, range(4 - len(axes), 4)))


def _euler_rhs(u: jax.Array, axes: Tuple[str, ...], mode: str,
               inv_dx) -> jax.Array:
    """The three flux tasks (paper Figure 5), each stage's halos exchanged
    inside the stage. Each direction's task needs only its OWN axis's halo
    (direction-split fluxes have no cross-dim couplings), so a 2-D mesh
    needs no corner messages."""
    carried = {d: a for a, d in _directions(axes)}
    return multi_dim_stencil(u, _llf_task(inv_dx),
                             [(d, carried.get(d)) for d in (1, 2, 3)],
                             width=EULER_WIDTH, periodic=True, mode=mode)


def _euler_rhs_carried(u: jax.Array, halos, dirs, inv_dx) -> jax.Array:
    """RHS with every decomposed direction's halos already in hand (one
    (lo, hi) pair per entry of `dirs`): a direction no mesh axis carries
    pads locally; each decomposed direction's faces consume its own carried
    pair, so no exchange sits on this stage's critical path, and the
    per-direction interior chunks are the independent work the ppermute
    pairs hide behind. The tasks are all formed, then summed x, y, z."""
    carried = {d: h for (_, d), h in zip(dirs, halos)}
    outs = []
    for d in (1, 2, 3):
        if d in carried:
            outs.append(stencil_with_halo_nd(
                u, [carried[d]], _llf_task(inv_dx, d), width=EULER_WIDTH,
                dims=(d,), subdomains=(EULER_SUBDOMAINS,)))
        else:
            outs.append(multi_dim_stencil(u, _llf_task(inv_dx), [(d, None)],
                                          width=EULER_WIDTH, periodic=True))
    with jax.named_scope(UPDATE):
        return functools.reduce(operator.add, outs)


def rk3_local_step(u: jax.Array, axes: Tuple[str, ...], mode: str,
                   inv_dx) -> Tuple[jax.Array, jax.Array]:
    """One 3-stage low-storage RK step (paper Code 8's rk loop): the CFL dt
    from the step's state, then per stage data-prep -> halo comm ->
    per-direction flux tasks -> update. Returns (U, dt)."""
    dt = _cfl_dt(u, inv_dx, axes)
    s = None
    for a, b in zip(_RK3_A, _RK3_B):
        u, s = _rk3_stage(u, s, _euler_rhs(u, axes, mode, inv_dx), dt, a, b)
    return u, dt


def rk3_local_step_carried(u: jax.Array, halos, dirs, inv_dx,
                           exchange_last: bool = True):
    """RK3 step with every decomposed direction's halos carried across
    stages: each stage consumes the pairs exchanged at the END of the
    previous stage and launches the next exchanges, in `dirs` order, the
    moment its U update lands, so every ppermute pair (all five components
    in one pair) flies behind the next stage's local tasks and interior
    chunks (the double-buffered analogue of Code 8's comm task).
    `exchange_last=False` peels the drain: the solve's final stage feeds no
    consumer, so its exchanges would be dead ppermute pairs. Returns
    (U, halos, dt)."""
    dt = _cfl_dt(u, inv_dx, tuple(a for a, _ in dirs))
    s = None
    for i, (a, b) in enumerate(zip(_RK3_A, _RK3_B)):
        rhs = _euler_rhs_carried(u, halos, dirs, inv_dx)
        u, s = _rk3_stage(u, s, rhs, dt, a, b)
        if exchange_last or i < len(_RK3_A) - 1:
            halos = exchange_halo_nd(u, dirs, EULER_WIDTH, periodic=True)
    return u, halos, dt


def euler_tgv_init(shape) -> jax.Array:
    """The compressible Taylor-Green vortex at Mach 0.1 (rho0 = U0 = 1) on
    the periodic box [0, BOX)^3, sampled at x_i = i dx, as the float32
    (5, nx, ny, nz) state rk3_solve takes: u = sin x cos y cos z,
    v = -cos x sin y cos z, w = 0, p = p0 + (cos 2x + cos 2y)(cos 2z + 2) / 16,
    rho = p / p0 with p0 = 1 / (GAMMA Ma^2)."""
    x, y, z = jnp.meshgrid(*[jnp.arange(n, dtype=jnp.float32) * (BOX / n)
                             for n in shape], indexing="ij")
    p0 = 1 / (GAMMA * 0.1 ** 2)
    u = jnp.sin(x) * jnp.cos(y) * jnp.cos(z)
    v = -jnp.cos(x) * jnp.sin(y) * jnp.cos(z)
    p = p0 + (jnp.cos(2 * x) + jnp.cos(2 * y)) * (jnp.cos(2 * z) + 2) / 16
    rho = p / p0
    e = p / (GAMMA - 1) + 0.5 * rho * (u * u + v * v)
    return jnp.stack([rho, rho * u, rho * v, jnp.zeros_like(u), e])


@functools.lru_cache(maxsize=128)
def _rk3_solver(mesh, axes, steps: int, inv_dx: Tuple[float, ...],
                mode: str):
    axes = normalize_mesh_axes(axes, "rk3_solve", (1, 2))
    dirs = _directions(axes)
    w = EULER_WIDTH

    def local(u):
        if (mode == "hdot" and all(u.shape[d] >= 4 * w for _, d in dirs)
                and steps > 0):
            halos = exchange_halo_nd(u, dirs, w, periodic=True)  # fill

            def body(carry, _):
                u, halos, dt = rk3_local_step_carried(*carry, dirs, inv_dx)
                return (u, halos), dt

            # drain peeled: the last step's last-stage exchanges are dead
            (u, halos), dts = lax.scan(body, (u, halos), None,
                                       length=steps - 1)
            u, _, dt = rk3_local_step_carried(u, halos, dirs, inv_dx,
                                              exchange_last=False)
            return u, jnp.concatenate([dts, dt[None]])

        # two-phase, or blocks too small for hdot: halos inside each stage
        def body(u, _):
            return rk3_local_step(u, axes, mode, inv_dx)
        return lax.scan(body, u, None, length=steps)

    spec = P(*((None,) * (4 - len(axes)) + axes))
    f = jax.shard_map(local, mesh=mesh, in_specs=spec, out_specs=(spec, P()))
    return jax.jit(f)


def rk3_solve(u0: jax.Array, mesh, mesh_axes, steps: int,
              mode: str = "hdot") -> Tuple[jax.Array, jax.Array]:
    """Run `steps` RK3 steps of the compressible Euler equations (CREAMS,
    paper §4.2) on the periodic box [0, BOX)^3; returns (U after `steps`
    steps, the dt of each step).

    `u0` is the GLOBAL conserved state (rho, rho u, rho v, rho w, E) of shape
    (5, nx, ny, nz); p = (GAMMA - 1)(E - rho |u|^2 / 2). Each step takes
    dt = CFL / max over cells of sum_d (|u_d| + c) / dx_d from its starting
    state (a max over the mesh), then three Williamson low-storage RK stages,
    each summing the three directions' LLF-split WENO5 flux tasks.

    `mesh_axes` is the unified solver topology contract: ``(z_axis,)`` — the
    paper's z-decomposed slabs — or a ``(y_axis, z_axis)`` pair — true 2-D
    (y, z) grid-mesh decomposition. Both run one path: hdot carries each
    decomposed direction's halos across stages (each direction-split task
    consumes only its own axis's pair, so the 2-D mesh needs no corner
    messages). One ppermute pair per axis per stage carries all five
    components."""
    with jax.profiler.TraceAnnotation(SOLVE_SPAN):
        axes = normalize_mesh_axes(mesh_axes, "rk3_solve", (1, 2))
        if u0.ndim != 4 or u0.shape[0] != 5:
            raise ValueError(f"rk3_solve: the state is (5, nx, ny, nz), got "
                             f"shape {u0.shape}")
        inv_dx = tuple(n / BOX for n in u0.shape[1:])
        return _rk3_solver(mesh, axes, steps, inv_dx, mode)(u0)


# ============================================================ HPCCG CG (§4.3)
def _window_sum(q: jax.Array, window: Tuple[int, ...], padding) -> jax.Array:
    """Sum over each `window` box of `q`, zero-padded by `padding` (one
    (lo, hi) pair per dim): a float32 window reduction on the vector unit."""
    return lax.reduce_window(q, jnp.zeros((), q.dtype), lax.add, window,
                             (1,) * q.ndim, padding)


def _sum27(q: jax.Array) -> jax.Array:
    """HPCCG's 27-point operator (diag=26, off-diag=-1) on a fully padded
    (nx+2, ny+2, nz+2) block; returns the (nx, ny, nz) interior as
    27·centre − W(q), W the 3×3×3 window sum."""
    return (27.0 * q[1:-1, 1:-1, 1:-1]
            - _window_sum(q, (3, 3, 3), ((0, 0),) * 3))


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
    axis, no corner messages. Returns (p_padded, lo_last, hi_last), the last
    two as 2-D planes: a trailing dim of 1 would lie on the 128 lanes."""
    with jax.named_scope(EXCHANGE):
        for a, d in zip(axes[:-1], dims[:-1]):
            p = pad_with_halo(p, a, 1, dim=d)
        lo, hi = exchange_halo(p, axes[-1], 1, dim=dims[-1], periodic=False)
    return p, lo[..., 0], hi[..., 0]


def _stencil27_matvec_chain(p: jax.Array, axes: Tuple[str, ...],
                            dims: Tuple[int, ...], mode: str,
                            halos=None) -> jax.Array:
    """y = A p with block decomposition over the mesh dims in `dims` (z,
    (y, z) or (x, y, z); the last is z, dim 2). `halos` is the
    :func:`_exchange_chain` triple, pre-exchanged by the pipelined CG.

    hdot applies A p = 27·p − W(p) over the whole local block: the interior
    task is W over the chain-padded block with zero z ghosts, so it reads no
    z halo; each face task is the 3×3 window sum of one received z plane,
    the only consumer of the last ppermute pair; the assemble adds each face
    sum to the block's first or last z plane. The padded block's shells are
    disjoint and the z planes carry every corner, so this is exact."""
    if halos is None:
        halos = _exchange_chain(p, axes, dims)
    p1, lo, hi = halos
    # x, y ghosts no mesh axis carries are zeros (global Dirichlet)
    pads = tuple((0, 0) if d in dims else (1, 1) for d in range(2))
    if mode != "hdot":
        with jax.named_scope(EXCHANGE):
            padded = jnp.pad(jnp.concatenate([lo[..., None], p1,
                                              hi[..., None]], axis=2),
                             pads + ((0, 0),))
        with jax.named_scope(INTERIOR):
            return _sum27(padded)
    with jax.named_scope(INTERIOR):
        box = _window_sum(p1, (3, 3, 3), pads + ((1, 1),))
    with jax.named_scope(FACES):
        f_lo, f_hi = (_window_sum(h, (3, 3), pads)[..., None]
                      for h in (lo, hi))
    with jax.named_scope(ASSEMBLE):
        # a select on the z index, not a plane update: it fuses into the one
        # pass over the block and leaves the layout of p to the compiler
        z = lax.broadcasted_iota(jnp.int32, p.shape, 2)
        edges = (jnp.where(z == 0, f_lo, 0.0)
                 + jnp.where(z == p.shape[2] - 1, f_hi, 0.0))
        return 27.0 * p - box - edges


def _ddot(a: jax.Array, b: jax.Array, axes: Tuple[str, ...],
          subdomains: int = 4) -> jax.Array:
    """paper Code 11: per-subdomain reduction(+) partials, then allreduce
    over the mesh `axes`."""
    with jax.named_scope(REDUCE):
        prod = (a * b).reshape(-1)
        chunks = jnp.array_split(prod, subdomains)
        partials = [jnp.sum(c, dtype=jnp.float64 if a.dtype == jnp.float64
                            else jnp.float32)
                    for c in chunks]
        return lax.psum(task_reduce(partials, "sum"), axes)


@functools.lru_cache(maxsize=128)
def _hpccg_solver(mesh, mesh_axes, iters: int, mode: str, subdomains: int):
    axes = normalize_mesh_axes(mesh_axes, "hpccg_solve", (1, 2, 3))
    # trailing grid dims carry the mesh: z for slabs, (y, z) for a pair,
    # (x, y, z) for a full 3-D mesh
    dims = tuple(range(3 - len(axes), 3))

    def local(b_loc):
        x = jnp.zeros_like(b_loc)
        r = b_loc
        p = r
        rtrans = _ddot(r, r, axes, subdomains)
        pipelined = mode == "hdot" and iters > 0

        def step(x, r, p, rtrans, halos):
            Ap = _stencil27_matvec_chain(p, axes, dims, mode, halos=halos)
            pAp = _ddot(p, Ap, axes, subdomains)
            with jax.named_scope(UPDATE):
                alpha = rtrans / pAp
                x = x + alpha * p          # waxpby tasks
                r = r - alpha * Ap
            rtrans_new = _ddot(r, r, axes, subdomains)
            with jax.named_scope(UPDATE):
                beta = rtrans_new / rtrans
                p = r + beta * p
            return x, r, p, rtrans_new

        if pipelined:
            def body(carry, _):
                x, r, p, rtrans, halos = carry
                x, r, p, rtrans = step(x, r, p, rtrans, halos)
                halos = _exchange_chain(p, axes, dims)  # the NEXT matvec's
                return (x, r, p, rtrans, halos), jnp.sqrt(rtrans)

            # drain peeled: the last iteration consumes its halos but feeds
            # no further matvec — same dead-exchange saving as halo_scan_nd
            (x, r, p, rtrans, halos), hist = lax.scan(
                body, (x, r, p, rtrans, _exchange_chain(p, axes, dims)), None,
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

    spec = P(*((None,) * (3 - len(axes)) + axes))
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
    Every topology runs one path, the sequential face-message chain
    (:func:`_exchange_chain`): each earlier dim is padded in order on the
    already-padded block, so the last dim's halo planes carry every corner
    coupling of the 27-point operator with one face ppermute pair per axis.

    hdot mode pipelines the matvec halo: the exchange(s) for iteration k+1
    are launched the moment p_{k+1} is formed, so they ride behind the two
    ddot allreduces, the waxpby tasks, and the next matvec's interior task
    — only the boundary-plane tasks of the next matvec wait on them.
    `subdomains` cuts only the dot products' partials (:func:`_ddot`); the
    matvec is one task over the block. The jitted solver is cached per
    (mesh, topology, iters, mode, subdomains) so repeated solves (and
    benchmark timings) pay compile once."""
    with jax.profiler.TraceAnnotation(SOLVE_SPAN):
        axes = normalize_mesh_axes(mesh_axes, "hpccg_solve", (1, 2, 3))
        return _hpccg_solver(mesh, axes, iters, mode, subdomains)(b)
