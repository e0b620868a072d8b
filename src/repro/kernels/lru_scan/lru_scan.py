"""Pallas TPU kernel: chunked gated linear recurrence  h_t = a_t*h_{t-1} + b_t.

Grid (batch, chunks) with the chunk axis innermost: TPU grids execute
sequentially, so the carry state lives in VMEM scratch across chunk steps —
exactly the HDOT hand-off between sequence subdomains. Inside the chunk the
recurrence runs as a width-vectorized fori_loop over time (VPU lanes carry the
`width` dimension; the recurrence itself is latency-bound, which is why the
chunked layout matters: it amortizes HBM traffic to one load/store per
element).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, o_ref, hlast_ref, h_scr, *, q: int, nc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)         # (1, w)

    def body(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)
        h = a_t[None, :] * h + b_t[None, :]
        o_ref[0, t, :] = h[0].astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, q, body, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == nc - 1)
    def _done():
        hlast_ref[0] = h.astype(hlast_ref.dtype)


def lru_scan_pallas(a: jax.Array, b: jax.Array, h0: Optional[jax.Array] = None,
                    chunk: int = 256,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """a, b: (batch, seq, width). Returns (h (batch, seq, width), h_last)."""
    bsz, l, w = a.shape
    chunk = min(chunk, l)
    if l % chunk != 0:
        raise ValueError(
            f"lru_scan_pallas: sequence length {l} is not divisible by "
            f"chunk={chunk} (a.shape={a.shape})")
    nc = l // chunk
    # h0/h_last travel as (b, 1, w) so each block's last two dims, (1, w),
    # equal the array's: a (1, w) block of a (b, w) array is refused by the
    # TPU lowering whenever b > 1
    h0 = (jnp.zeros((bsz, 1, w), jnp.float32) if h0 is None
          else h0.reshape(bsz, 1, w))

    kernel = functools.partial(_kernel, q=chunk, nc=nc)
    h, hlast = pl.pallas_call(
        kernel,
        grid=(bsz, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, w), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, w), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, 1, w), lambda ib, ic: (ib, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, w), lambda ib, ic: (ib, ic, 0)),
            pl.BlockSpec((1, 1, w), lambda ib, ic: (ib, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, l, w), b.dtype),
            jax.ShapeDtypeStruct((bsz, 1, w), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, w), jnp.float32)],
        interpret=interpret,
    )(a, b, h0)
    return h, hlast.reshape(bsz, w)
