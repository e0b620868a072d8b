"""Plain float32 reference of the CREAMS solver (HDOT paper §4.2): the
compressible Euler equations on a periodic box, LLF flux splitting with
WENO5-JS reconstruction per direction, and Williamson's low-storage RK3 with
a CFL time step.

A straightforward ``jax.numpy`` forward pass on the global array: periodic
neighbours by ``jnp.roll``, each direction's flux written out, no chunks,
halos, ``shard_map`` or scan tricks beyond the loop over steps. It shares no
code with ``repro.core``.

The shifts stay ``jnp.roll``, the plainest periodic form, because this
reference runs only in the tests on the CPU, where it computes right. On a
TPU v5e at 256^3, steps written with ``jnp.roll`` were compiled to wrong
numbers (PERF.md, Open questions), so ``bench/apps/creams.py``, whose copy
runs on the chip, takes static slices of a wrapped copy instead.

The scheme is the textbook one (Shu, ICASE Report 97-65; Jiang & Shu,
J. Comput. Phys. 126, 1996):

- U = (rho, rho u, rho v, rho w, E), p = (GAMMA - 1)(E - rho |u|^2 / 2),
  c = sqrt(GAMMA p / rho), GAMMA = 1.4;
- at face i+1/2 of direction d, alpha = max(|u_d| + c) over cells i-2..i+3,
  F+- = (F_d +- alpha U) / 2 on those cells, the face flux is WENO5-JS of
  F+ from cells i-2..i+2 plus the mirrored WENO5-JS of F- from i-1..i+3
  (linear weights 1/10, 6/10, 3/10, epsilon 1e-6, power 2);
- dU/dt = -sum_d (F_{i+1/2} - F_{i-1/2}) / dx_d;
- each step: dt = CFL / max(sum_d (|u_d| + c) / dx_d), then three stages
  S = A_k S + dt dU/dt, U = U + B_k S.

Departures from CREAMS, which the HDOT paper names but does not specify:
inviscid single-species Euler (CREAMS solves reacting multi-species
Navier-Stokes); the reconstruction is component-wise, not characteristic-
wise, and its order (5) is assumed; float32, where CFD codes compute in
double.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

RK3_A = (0.0, -5 / 9, -153 / 128)
RK3_B = (1 / 3, 15 / 16, 8 / 15)
EPS = 1e-6
GAMMA = 1.4
CFL = 0.5
LENGTH = 2 * math.pi  # the periodic box [0, LENGTH)^3


def primitives(U):
    rho, mx, my, mz, E = U
    u, v, w = mx / rho, my / rho, mz / rho
    p = (GAMMA - 1) * (E - 0.5 * rho * (u * u + v * v + w * w))
    c = jnp.sqrt(GAMMA * p / rho)
    return rho, (u, v, w), p, c


def flux(U, d):
    """(F_d, |u_d| + c) on every cell."""
    rho, mx, my, mz, E = U
    _, (u, v, w), p, c = primitives(U)
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


def weno5(vm2, vm1, v0, vp1, vp2):
    """WENO5-JS value at the face i+1/2 from cells i-2..i+2, upwind from
    the left."""
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


def divergence(U, d, dx):
    """-(F_{i+1/2} - F_{i-1/2}) / dx along direction d."""
    F, speed = flux(U, d)

    def cell(x, k, axis):  # x on cell i + k, for every i
        return jnp.roll(x, -k, axis=axis)

    alpha = cell(speed, -2, d)
    for k in range(-1, 4):
        alpha = jnp.maximum(alpha, cell(speed, k, d))
    plus = {k: 0.5 * (cell(F, k, d + 1) + alpha * cell(U, k, d + 1))
            for k in range(-2, 3)}
    minus = {k: 0.5 * (cell(F, k, d + 1) - alpha * cell(U, k, d + 1))
             for k in range(-1, 4)}
    face = (weno5(plus[-2], plus[-1], plus[0], plus[1], plus[2])
            + weno5(minus[3], minus[2], minus[1], minus[0], minus[-1]))
    return -(face - jnp.roll(face, 1, axis=d + 1)) / dx


def rhs(U, dx):
    return (divergence(U, 0, dx[0]) + divergence(U, 1, dx[1])
            + divergence(U, 2, dx[2]))


def cfl_dt(U, dx):
    _, (u, v, w), _, c = primitives(U)
    rate = ((jnp.abs(u) + c) / dx[0] + (jnp.abs(v) + c) / dx[1]
            + (jnp.abs(w) + c) / dx[2])
    return CFL / jnp.max(rate)


def spacing(shape):
    return tuple(LENGTH / n for n in shape[1:])


@functools.partial(jax.jit, static_argnums=1)
def _solve(U, steps):
    dx = spacing(U.shape)

    def step(U, _):
        dt = cfl_dt(U, dx)
        S = jnp.zeros_like(U)
        for a, b in zip(RK3_A, RK3_B):
            S = a * S + dt * rhs(U, dx)
            U = U + b * S
        return U, dt

    return lax.scan(step, U, None, length=steps)


def solve(U0, steps):
    """(U after `steps` steps, dt of each step), in float32."""
    with jax.default_matmul_precision("highest"):
        return _solve(U0.astype(jnp.float32), steps)


def conserved(rho, u, v, w, p):
    """U from primitive fields."""
    E = p / (GAMMA - 1) + 0.5 * rho * (u * u + v * v + w * w)
    return jnp.stack([rho, rho * u, rho * v, rho * w, E])


def random_state(key, shape):
    """A seeded float32 state away from vacuum at Mach ~0.5: rho and p
    within 10% of 1, velocities uniform in [-0.5, 0.5)."""
    kr, kv, kp = jax.random.split(key, 3)
    rho = 1 + 0.1 * jax.random.uniform(kr, shape, jnp.float32, -1, 1)
    vel = jax.random.uniform(kv, (3,) + tuple(shape), jnp.float32, -0.5, 0.5)
    p = 1 + 0.1 * jax.random.uniform(kp, shape, jnp.float32, -1, 1)
    return conserved(rho, vel[0], vel[1], vel[2], p)


def kinetic_energy(U):
    return 0.5 * jnp.sum((U[1] ** 2 + U[2] ** 2 + U[3] ** 2) / U[0])
