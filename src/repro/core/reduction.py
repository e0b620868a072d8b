"""Hierarchical task->process reductions (paper §3.3, Code 5).

The paper computes reductions at two levels: concurrent tasks privately reduce
subdomain partials (OmpSs-2 `reduction(MAX:rlocal)`), then one communication
task performs the process-level `MPI_Allreduce`. The TPU analogue:

  task level     = per-subdomain partials reduced locally (tree reduction of
                   chunk results inside the shard)
  process level  = `lax.psum` / `lax.pmax` over mesh axes, optionally staged
                   hierarchically (reduce-scatter in-pod -> all-reduce
                   cross-pod -> all-gather in-pod) so the slow cross-pod hop
                   carries 1/pod_size of the bytes.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.halo import REDUCE

AxisNames = Union[str, Sequence[str]]

_OPS = {
    "sum": (jnp.add, lax.psum),
    "max": (jnp.maximum, lax.pmax),
    "min": (jnp.minimum, lax.pmin),
}


def task_reduce(partials: Sequence[jax.Array], op: str = "sum") -> jax.Array:
    """Tree-reduce task-level (subdomain) partials inside one shard.

    Mirrors OmpSs-2's `reduction` clause: each subdomain task produced a
    private partial; this combines them in O(log n) dataflow depth so the
    combine itself exposes no serialization."""
    combine, _ = _OPS[op]
    items = list(partials)
    if not items:
        # bare asserts vanish under `python -O`; this is a caller bug that
        # must surface loudly on the reduction hot path
        raise ValueError("task_reduce needs at least one partial")
    with jax.named_scope(REDUCE):
        while len(items) > 1:
            nxt = []
            for i in range(0, len(items) - 1, 2):
                nxt.append(combine(items[i], items[i + 1]))
            if len(items) % 2:
                nxt.append(items[-1])
            items = nxt
    return items[0]


def process_allreduce(x: jax.Array, axes: AxisNames, op: str = "sum") -> jax.Array:
    """Process-level collective (the paper's MPI_Allreduce) over mesh axes."""
    _, coll = _OPS[op]
    return coll(x, axes)


def hdot_reduce(partials: Sequence[jax.Array], axes: AxisNames,
                op: str = "sum") -> jax.Array:
    """Full paper pattern: task-level tree reduce -> process-level allreduce."""
    with jax.named_scope(REDUCE):
        return process_allreduce(task_reduce(partials, op), axes, op)


def hierarchical_allreduce(x: jax.Array, inner_axis: str,
                           outer_axis: Optional[str] = None,
                           scatter_dim: int = 0,
                           compress: Optional[Callable] = None,
                           decompress: Optional[Callable] = None) -> jax.Array:
    """Bandwidth-staged allreduce for multi-pod meshes.

    reduce-scatter over `inner_axis` (fast in-pod ICI), then all-reduce over
    `outer_axis` (slow cross-pod hop, optionally compressed), then all-gather
    over `inner_axis`. Equivalent to psum over both axes; cross-pod bytes are
    reduced by  inner_size x (x compression ratio).

    `compress/decompress` wrap ONLY the cross-pod hop (e.g. int8 error-feedback
    from repro.optim.compression)."""
    if x.shape[scatter_dim] % lax.axis_size(inner_axis) != 0:
        # fall back: shape not tileable -> plain fused psum (still correct)
        axes = (inner_axis,) if outer_axis is None else (inner_axis, outer_axis)
        return lax.psum(x, axes)
    part = lax.psum_scatter(x, inner_axis, scatter_dimension=scatter_dim, tiled=True)
    if outer_axis is not None:
        if compress is not None:
            payload = compress(part)
            payload = jax.tree.map(lambda t: lax.psum(t, outer_axis), payload)
            part = decompress(payload)
        else:
            part = lax.psum(part, outer_axis)
    return lax.all_gather(part, inner_axis, axis=scatter_dim, tiled=True)
