"""CREAMS (HDOT paper §4.2) as a compressible Euler solver: the timed path,
its plain reference and its required work.

The timed path is ``repro.core.stencil.rk3_solve(..., mode="hdot")``:
``steps`` steps of Williamson's low-storage RK3 on
the conserved state (rho, rho u, rho v, rho w, E) of a periodic box, each
step's dt = CFL / max over cells of sum_d (|u_d| + c) / dx_d taken from its
starting state (partial maxes per task, then a max over the mesh), and each
stage summing the three directions' flux tasks: local Lax-Friedrichs
splitting with WENO5-JS reconstruction, halo width 3. The state is laid out
(5, nx, ny, nz) and decomposed on a (y, z) mesh, whose halos are carried
across stages on both axes. The reference shares no code with the program:
a jnp forward pass on the global array, its periodic neighbours static
slices of a wrapped copy.

The input is the compressible Taylor-Green vortex at Mach ``mach`` on
[0, 2 pi)^3, built on the device, with the traffic's seeded velocity
perturbation (in units of U0) added to u, v and w.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.stencil import BOX, CFL, GAMMA, rk3_solve
from repro.launch.mesh import GRID_AXES, make_grid_mesh

import generate

RK3_A = (0.0, -5 / 9, -153 / 128)
RK3_B = (1 / 3, 15 / 16, 8 / 15)
EPS = 1e-6
# the program's semantics; a configuration asking for others is refused
SEMANTICS = {"cfl": CFL, "gamma": GAMMA, "domain_length": BOX,
             "weno_order": 5, "viscosity": 0.0, "species": 1,
             "reconstruction": "component-wise WENO5-JS"}


def make_mesh(shape, devices):
    if len(shape) != 2:
        raise ValueError(f"creams decomposes on a (y, z) mesh, got {shape}")
    return make_grid_mesh(*shape, devices=devices)


def global_shape(cfg: dict, mesh) -> tuple:
    """Weak scaling: every chip holds a ``local_grid`` block; the mesh
    splits y and z. The leading axis holds the five conserved fields."""
    nx, ny, nz = cfg["local_grid"]
    rows, cols = mesh.devices.shape
    return (5, nx, ny * rows, nz * cols)


def _sharding(mesh):
    return NamedSharding(mesh, P(None, None, *GRID_AXES))


def _conserved(rho, u, v, w, p, gamma):
    e = p / (gamma - 1) + 0.5 * rho * (u * u + v * v + w * w)
    return jnp.stack([rho, rho * u, rho * v, rho * w, e])


def make_input(cfg: dict, traffic: dict, mesh, key) -> jax.Array:
    """The Taylor-Green vortex, sampled at x_i = i dx: u = U0 sin x cos y
    cos z, v = -U0 cos x sin y cos z, w = 0, p = p0 + rho0 U0^2 / 16
    (cos 2x + cos 2y)(cos 2z + 2), rho = rho0 p / p0, p0 = rho0 U0^2 /
    (gamma Ma^2); plus the drawn perturbation times U0 on u, v, w."""
    shape = global_shape(cfg, mesh)
    dtype = jnp.dtype(cfg["dtype"])
    sharding = _sharding(mesh)
    dvel = generate.draw(key, (3,) + shape[1:], dtype, traffic["input"], sharding)
    gamma, rho0, u0 = cfg["gamma"], cfg["rho0"], cfg["u0"]
    p0 = rho0 * u0 ** 2 / (gamma * cfg["mach"] ** 2)

    def build(dvel):
        x, y, z = (lax.broadcasted_iota(dtype, shape[1:], d)
                   * (cfg["domain_length"] / n) for d, n in enumerate(shape[1:]))
        u = u0 * (jnp.sin(x) * jnp.cos(y) * jnp.cos(z) + dvel[0])
        v = u0 * (-jnp.cos(x) * jnp.sin(y) * jnp.cos(z) + dvel[1])
        w = u0 * dvel[2]
        p = p0 + rho0 * u0 ** 2 / 16 * (jnp.cos(2 * x) + jnp.cos(2 * y)) * (jnp.cos(2 * z) + 2)
        return _conserved(rho0 * p / p0, u, v, w, p, gamma)

    return jax.jit(build, out_shardings=sharding)(dvel)


def solve(cfg: dict, mesh, u):
    """One solve: (U after ``steps`` steps, the dt of each step)."""
    for k, v in SEMANTICS.items():
        if cfg[k] != v:
            raise ValueError(f"the program computes {k} = {v!r}, not {cfg[k]!r}")
    return rk3_solve(u, mesh, GRID_AXES, cfg["steps"], mode="hdot")


def carry(out):
    """A time-stepper continues from the state the last solve reached."""
    return out[0]


def _primitives(U, gamma):
    rho, mx, my, mz, E = U
    u, v, w = mx / rho, my / rho, mz / rho
    p = (gamma - 1) * (E - 0.5 * rho * (u * u + v * v + w * w))
    c = jnp.sqrt(gamma * p / rho)
    return (u, v, w), p, c


def _flux(U, d, gamma):
    rho, mx, my, mz, E = U
    (u, v, w), p, c = _primitives(U, gamma)
    if d == 0:
        F = (mx, mx * u + p, my * u, mz * u, (E + p) * u)
        speed = jnp.abs(u) + c
    elif d == 1:
        F = (my, mx * v, my * v + p, mz * v, (E + p) * v)
        speed = jnp.abs(v) + c
    else:
        F = (mz, mx * w, my * w, mz * w + p, (E + p) * w)
        speed = jnp.abs(w) + c
    return jnp.stack(F), speed


def _weno5(vm2, vm1, v0, vp1, vp2):
    p0 = (2 * vm2 - 7 * vm1 + 11 * v0) / 6
    p1 = (-vm1 + 5 * v0 + 2 * vp1) / 6
    p2 = (2 * v0 + 5 * vp1 - vp2) / 6
    b0 = 13 / 12 * (vm2 - 2 * vm1 + v0) ** 2 + 1 / 4 * (vm2 - 4 * vm1 + 3 * v0) ** 2
    b1 = 13 / 12 * (vm1 - 2 * v0 + vp1) ** 2 + 1 / 4 * (vm1 - vp1) ** 2
    b2 = 13 / 12 * (v0 - 2 * vp1 + vp2) ** 2 + 1 / 4 * (3 * v0 - 4 * vp1 + vp2) ** 2
    a0 = (1 / 10) / (EPS + b0) ** 2
    a1 = (6 / 10) / (EPS + b1) ** 2
    a2 = (3 / 10) / (EPS + b2) ** 2
    return (a0 * p0 + a1 * p1 + a2 * p2) / (a0 + a1 + a2)


def _divergence(U, d, dx, gamma):
    """-(F_{i+1/2} - F_{i-1/2}) / dx along direction d: at face i+1/2,
    alpha = max |u_d| + c over cells i-2..i+3 and F+- = (F +- alpha U) / 2
    on them; F+ by WENO5-JS from cells i-2..i+2, F- mirrored from i-1..i+3.

    The periodic neighbours are static slices of a copy wrapped by three
    cells at both ends. Steps that shifted by ``jnp.roll`` instead (as
    ``tests/euler_reference.py`` does on the CPU) were compiled to wrong
    numbers for a TPU v5e at 256^3: each direction alone was exact, but the
    steps broke conservation (PERF.md, Open questions)."""
    F, speed = _flux(U, d, gamma)
    n = U.shape[d + 1]

    def wrap(x, axis):  # three periodic ghost cells at both ends of `axis`
        return jnp.concatenate([lax.slice_in_dim(x, n - 3, n, axis=axis), x,
                                lax.slice_in_dim(x, 0, 3, axis=axis)], axis=axis)

    F, Uw, speed = wrap(F, d + 1), wrap(U, d + 1), wrap(speed, d)

    def cell(x, k, axis):  # x on cell i + k, for the faces i + 1/2, i = -1..n-1
        return lax.slice_in_dim(x, 2 + k, 3 + k + n, axis=axis)

    alpha = cell(speed, -2, d)
    for k in range(-1, 4):
        alpha = jnp.maximum(alpha, cell(speed, k, d))
    plus = {k: 0.5 * (cell(F, k, d + 1) + alpha * cell(Uw, k, d + 1))
            for k in range(-2, 3)}
    minus = {k: 0.5 * (cell(F, k, d + 1) - alpha * cell(Uw, k, d + 1))
             for k in range(-1, 4)}
    face = (_weno5(plus[-2], plus[-1], plus[0], plus[1], plus[2])
            + _weno5(minus[3], minus[2], minus[1], minus[0], minus[-1]))
    return -(lax.slice_in_dim(face, 1, n + 1, axis=d + 1)
             - lax.slice_in_dim(face, 0, n, axis=d + 1)) / dx


def _cfl_dt(U, dx, cfl, gamma):
    (u, v, w), _, c = _primitives(U, gamma)
    rate = ((jnp.abs(u) + c) / dx[0] + (jnp.abs(v) + c) / dx[1]
            + (jnp.abs(w) + c) / dx[2])
    return cfl / jnp.max(rate)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _euler(U, steps: int, cfl: float, gamma: float, length: float, dtype):
    U = U.astype(dtype)
    dx = tuple(length / n for n in U.shape[1:])

    def step(U, _):
        dt = _cfl_dt(U, dx, cfl, gamma)
        S = jnp.zeros_like(U)
        for a, b in zip(RK3_A, RK3_B):
            rhs = (_divergence(U, 0, dx[0], gamma) + _divergence(U, 1, dx[1], gamma)
                   + _divergence(U, 2, dx[2], gamma))
            S = a * S + dt * rhs
            U = U + b * S
        return U, dt

    return lax.scan(step, U, None, length=steps)


def reference(cfg: dict, mesh, u, dtype=jnp.float32):
    """Plain compressible Euler RK3 in `dtype` on the global array: U after
    ``steps`` steps and the dt of each step. Departures from the source are
    the configuration's (``departures``)."""
    with jax.default_matmul_precision("highest"):
        return _euler(u, cfg["steps"], float(cfg["cfl"]), float(cfg["gamma"]),
                      float(cfg["domain_length"]), jnp.dtype(dtype))


@jax.jit
def _errors(u, dts, u_ref, dts_ref):
    u, u_ref = u.astype(jnp.float32), u_ref.astype(jnp.float32)
    dts, dts_ref = dts.astype(jnp.float32), dts_ref.astype(jnp.float32)
    cells = tuple(range(1, u.ndim))
    per_field = (jnp.max(jnp.abs(u - u_ref), axis=cells)
                 / jnp.max(jnp.abs(u_ref), axis=cells))
    return jnp.max(per_field), jnp.max(jnp.abs(dts - dts_ref) / jnp.abs(dts_ref))


def compare(out, ref) -> dict:
    """``state_err``: the largest, over the five fields, of the field's
    largest cell error over its largest reference cell; ``dt_err``: the
    largest relative error of a step's dt."""
    state, dt = _errors(out[0], out[1], ref[0], ref[1])
    return {"state_err": float(state), "dt_err": float(dt)}


# Operations per cell, counted from the reference's formulas above (a
# division or a square root is one operation):
PRIMITIVES = 15              # 3 divisions, |u|^2 5, p 4, c 3
FLUX = PRIMITIVES + 6 + 2    # F_d 6 (3 products, 2 sums with p, 1 product), |u_d| + c 2
SPLIT = 5 + 2 * 5 * 5 * 3    # alpha: 5 maxima; F+- on 5 cells x 5 fields, 3 each
WENO5 = 67                   # candidates 17, indicators 33, weights 9, blend 8
DIRECTION = FLUX + SPLIT + 5 * (2 * WENO5 + 1) + 5 * 3  # + face sum, difference 3
STAGE = 3 * DIRECTION + 2 * 5 + 5 * 5                   # + direction sums, S and U
CFL = PRIMITIVES + 3 * 3 + 2 + 1                        # rate 11, max 1
# Bytes per cell, in fields of the 5-field state: stage 1 reads U and
# writes U and S (A_1 = 0, so S is not read); stage 2 reads and writes both;
# stage 3 reads both and writes U (S restarts each step); the dt reads U.
FIELDS_PER_STEP = 15 + 20 + 15 + 5


def work(cfg: dict) -> dict:
    """Operations and HBM bytes one solve needs on one chip.

    Bytes: the low-storage RK3 carries two 5-field states, U and S; the
    flux tasks read U's neighbours in the pass that reads U. Stage 1 reads
    U and writes U and S (its A is 0, so the old S is never read): 15
    fields. Stage 2 reads and writes both: 20. Stage 3 reads both and
    writes U; its S is dropped, as each step restarts S: 15. The dt of a
    step is a max over every cell of U before the step's first stage can
    update, so it takes one more read of U: 5. Least: 55 fields per cell
    per step (220 B in float32).

    Operations per cell, from the reference's formulas: per direction the
    primitives (15: three divisions, |u|^2 5, p 4, c 3), the flux and
    |u_d| + c (8), the splitting speed (5 maxima over 6 cells) and F+- on
    the five cells of each side for five fields (150), two WENO5-JS
    reconstructions per field (67 each: candidates 17, smoothness
    indicators 33, nonlinear weights 9, blend 8) and their sum (5 x 135),
    and the flux difference over dx (15): 868. Per stage three directions,
    their sums (10) and the S and U updates (25): 2639. Per step three
    stages and the dt's rate and max (27): 7944. At about 36 operations per
    byte against the chip's 240 FLOP per byte of bf16 matrix peak, the byte
    bound sets the least time here, though the vector unit does the work:
    ``mfu`` is then a share of the byte bound."""
    cells = math.prod(cfg["local_grid"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    steps = cfg["steps"]
    return {"flops": cells * steps * (3 * STAGE + CFL),
            "bytes": item * cells * steps * FIELDS_PER_STEP}
