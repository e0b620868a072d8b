"""The one input generator every traffic file feeds.

A traffic file (``bench/traffic/<name>.json``) is data only:

- ``input``: the distribution the solve's input is drawn from, on the
  device, from ``--seed``: ``{"kind": "uniform", "low", "high"}`` or
  ``{"kind": "normal", "mean", "std"}``;
- ``carry``: ``"output"`` feeds each solve the previous solve's result (a
  time-stepper continuing), ``"input"`` solves the same input again (a
  fixed right-hand side).

Other keys, such as a ``note``, are prose for the reader.

The mesh a cell lays its grid on is the cell's, not the traffic's: it is in
``bench/workloads/<cell>.json``.
"""
from __future__ import annotations

import jax

CARRIES = ("output", "input")


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number: the low 32 bits seed the key and
    the bits above them are folded in, so seeds past 2**32 stay distinct."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def draw(key: jax.Array, shape, dtype, dist: dict, sharding) -> jax.Array:
    """An array of `shape` drawn from `dist`, made on the device in one
    jitted call and laid out by `sharding`, so no device holds more than its
    shard. The key is an argument, so one compiled program serves every
    seed."""
    kind = dist["kind"]
    if kind == "uniform":
        def make(k):
            return jax.random.uniform(k, shape, dtype, dist["low"], dist["high"])
    elif kind == "normal":
        def make(k):
            return dist["mean"] + dist["std"] * jax.random.normal(k, shape, dtype)
    else:
        raise ValueError(f"unknown input distribution {kind!r}")
    return jax.jit(make, out_shardings=sharding)(key)


def validate(traffic: dict) -> dict:
    if traffic.get("carry") not in CARRIES:
        raise ValueError(f"traffic carry must be one of {CARRIES}, "
                         f"got {traffic.get('carry')!r}")
    return traffic
