"""Narrow-wire gradient codecs: int8 error-feedback for the cross-pod hop,
plus bf16/fp8 wire codecs (the WIRE-WIDEN lint fix path).

The slow inter-pod link carries gradients quantized to int8 with a per-tensor
scale (4x fewer bytes than fp32, 2x fewer than bf16); the quantization error
is fed back into the next step's gradient (error feedback, cf. 1-bit
SGD/EF-SGD), which keeps SGD/Adam convergence unbiased in practice.

Used by core.reduction.hierarchical_allreduce(compress=..., decompress=...)
— only the cross-pod all-reduce sees compressed payloads; in-pod
reduce-scatter/all-gather stay full precision.

NOTE (summation semantics): the psum over pods adds int32-accumulated int8
payloads with a shared max-scale, so the reduce is exact in the quantized
domain.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

PyTree = Any


def int8_compress(x: jax.Array, axis_name: str | None = None) -> Dict[str, jax.Array]:
    """Quantize to int8 with a per-tensor scale. When `axis_name` is given the
    scale is pmax'd across the axis so every participant shares one scale and
    the subsequent integer psum is exact."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    # int16 payload: the cross-pod psum of int8-valued entries cannot overflow
    # for <= 256 pods (127 * 256 = 32512 < 2^15) and moves HALF the bytes of
    # f32 (the point of compressing the slow hop)
    return {"q": q.astype(jnp.int16), "scale": scale}


def int8_decompress(payload: Dict[str, jax.Array]) -> jax.Array:
    return payload["q"].astype(jnp.float32) * payload["scale"]


def make_crosspod_codec(axis_name: str):
    """(compress, decompress) pair for hierarchical_allreduce: scale is shared
    (pmax) across the pod axis and NOT psum'd (only q is reduced)."""

    def compress(x):
        p = int8_compress(x, axis_name)
        return {"q": p["q"], "scale": p["scale"] * 0.0 + p["scale"]}  # keep tree

    def decompress(p):
        # q was psum'd over the axis; scale was psum'd too -> divide by count
        n = jax.lax.axis_size(axis_name)
        return p["q"].astype(jnp.float32) * (p["scale"] / n)

    return compress, decompress


def ef_compress_update(g: jax.Array, err: jax.Array,
                       axis_name: str | None = None,
                       compress=None, decompress=None,
                       ) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Error-feedback step: compress (g + err); return (payload, new_err).

    Defaults to the int8 codec; pass any (compress, decompress) pair from
    ``wire_codec`` to error-feed a bf16 or fp8 wire instead."""
    compress = compress or int8_compress
    decompress = decompress or int8_decompress
    target = g.astype(jnp.float32) + err
    payload = compress(target, axis_name)
    new_err = target - decompress(payload)
    return payload, new_err


# --------------------------------------------------------- narrow wire dtypes
# The sanctioned fix path for the linter's WIRE-WIDEN finding (gradients
# crossing a collective wider than the param spec): re-narrow the wire with
# one of these codecs instead of letting XLA's f32 accumulator width leak
# onto the interconnect. bf16 is a pure cast (no scale state, safe to psum
# directly — reduction happens at f32 after decode on each hop); fp8 (e4m3)
# carries a shared per-tensor scale like int8 but is NOT integer-exact under
# psum, so use it on point-to-point / gather hops or with error feedback.
_FP8_DTYPE = jnp.float8_e4m3fn   # 4-bit exponent / 3-bit mantissa
_FP8_MAX = float(jnp.finfo(_FP8_DTYPE).max)   # 448.0


def bf16_compress(x: jax.Array,
                  axis_name: str | None = None) -> Dict[str, jax.Array]:
    del axis_name  # no shared state: bf16 keeps f32's exponent range
    return {"q": x.astype(jnp.bfloat16)}


def bf16_decompress(payload: Dict[str, jax.Array]) -> jax.Array:
    return payload["q"].astype(jnp.float32)


def fp8_compress(x: jax.Array,
                 axis_name: str | None = None) -> Dict[str, jax.Array]:
    """Quantize to float8_e4m3fn with a per-tensor scale (pmax-shared across
    `axis_name`, same contract as int8_compress)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    if axis_name is not None:
        amax = jax.lax.pmax(amax, axis_name)
    scale = jnp.maximum(amax, 1e-12) / _FP8_MAX
    q = (x.astype(jnp.float32) / scale).astype(_FP8_DTYPE)
    return {"q": q, "scale": scale}


def fp8_decompress(payload: Dict[str, jax.Array]) -> jax.Array:
    return payload["q"].astype(jnp.float32) * payload["scale"]


WIRE_CODECS = {
    "bf16": (bf16_compress, bf16_decompress),
    "fp8": (fp8_compress, fp8_decompress),
    "int8": (int8_compress, int8_decompress),
}


def wire_codec(kind: str):
    """(compress, decompress) pair by wire-dtype name: bf16 | fp8 | int8."""
    try:
        return WIRE_CODECS[kind]
    except KeyError:
        raise ValueError(
            f"unknown wire codec {kind!r}; available: "
            f"{', '.join(sorted(WIRE_CODECS))}") from None
