"""Double-buffered all-to-all: the `halo_scan_nd` schedule applied to a2a.

An expert-parallel MoE layer moves every routed token twice through a single
monolithic ``all_to_all`` pair (dispatch there, combine back) — the one
collective that dominates large-MoE step time, and in the monolithic form the
exact "bulk communication with zero overlap window" shape the HDOT paper
taskifies away. `a2a_scan` applies the same move as
`core.halo.halo_scan_nd`: over-decompose the transfer along one dim into
``chunks`` slices and schedule

    dispatch a2a(k+1)  ||  compute(k)  ||  combine a2a(k-1)

so every slice's wire time sits inside a neighbor slice's compute. The
prologue (first dispatch) and drain (last combine) are peeled exactly like
halo_scan_nd's first/last exchange.

Trace order per step k (prologue ``dispatch(0)`` already issued):

    dispatch(k+1)        # next slice leaves BEFORE this slice's compute
    y_k = compute(recv_k)
    combine(y_k)         # this slice streams back while k+1 computes

``chunks=1`` emits exactly the monolithic two-a2a program — zero slice or
concat ops — so every existing caller/test is an equivalence oracle for the
chunked path. Chunking is value-preserving whenever ``compute_fn`` treats the
sliced dim elementwise (slicing commutes with both a2as and with the
per-slice compute), which the expert FFN does: its einsums contract only the
feature dim, never the capacity dim being sliced.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def a2a_scan(x: jax.Array,
             compute_fn: Callable[[jax.Array, int], jax.Array],
             axis_name: str, *, chunks: int = 1, dim: int,
             split_axis: int = 0, concat_axis: int = 0) -> jax.Array:
    """dispatch-a2a -> compute -> combine-a2a, double-buffered over ``dim``.

    x          : per-shard array inside a shard_map body.
    compute_fn : (received_slice, k) -> result slice, same rank, same extent
                 along ``dim``. Must be elementwise along ``dim`` for chunking
                 to preserve values.
    axis_name  : mesh axis of both all_to_alls.
    chunks     : number of capacity slices Q. 1 = monolithic (today's
                 schedule); must divide ``x.shape[dim]``.
    dim        : dim to over-decompose (NOT the a2a split/concat dim).
    split_axis / concat_axis : forwarded to both ``lax.all_to_all`` calls.
    """
    if chunks == 1:
        recv = lax.all_to_all(x, axis_name, split_axis, concat_axis)
        return lax.all_to_all(compute_fn(recv, 0), axis_name,
                              split_axis, concat_axis)
    n = x.shape[dim]
    if chunks < 1 or n % chunks != 0:
        raise ValueError(
            f"a2a_scan: chunks={chunks} must be >=1 and divide "
            f"x.shape[{dim}]={n} (x.shape={x.shape})")
    q = n // chunks

    def dispatch(k: int) -> jax.Array:
        sl = lax.slice_in_dim(x, k * q, (k + 1) * q, axis=dim)
        return lax.all_to_all(sl, axis_name, split_axis, concat_axis)

    recv = dispatch(0)                      # prologue: slice 0 on the wire
    outs = []
    for k in range(chunks):
        # issue slice k+1's dispatch BEFORE touching slice k's tokens — the
        # dataflow leaves XLA free to run it under compute_fn(k)
        nxt = dispatch(k + 1) if k + 1 < chunks else None
        y = compute_fn(recv, k)
        # combine streams back while slice k+1 computes; the last combine is
        # the drain (nothing left to hide it behind)
        outs.append(lax.all_to_all(y, axis_name, split_axis, concat_axis))
        recv = nxt
    return jnp.concatenate(outs, axis=dim)
