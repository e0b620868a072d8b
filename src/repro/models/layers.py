"""Param-spec system + common layers (norms, rope, MLP).

Parameters are plain dict pytrees. Every module publishes a matching tree of
:class:`ParamSpec` (shape + logical sharding axes + initializer), from which we
derive (a) materialized params for real runs, (b) ShapeDtypeStructs +
NamedShardings for the dry-run — the same "declare the decomposition once,
reuse it at every level" discipline HDOT prescribes for domains.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sharding.rules import with_logical

PyTree = Any


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical sharding axes (len == ndim)
    dtype: Any = jnp.bfloat16
    init: str = "normal"                     # normal | zeros | ones
    scale: Optional[float] = None            # None -> 1/sqrt(fan_in)
    # Layer provenance: forward depth of the (sub)module owning this param.
    # Higher depth = closer to the loss = its gradient is ready EARLIER in the
    # backward pass. core.overlap uses it to cut grad-sync buckets along layer
    # boundaries and emit their collectives last-backward-first. A scanned
    # (stacked) layer tree is one depth: lax.scan's backward materializes the
    # whole stacked gradient at once, so there is no per-layer early release
    # to order within it.
    layer: Optional[int] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_paths(tree: PyTree, prefix=()) -> Dict[Tuple, ParamSpec]:
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_leaf_paths(tree[k], prefix + (k,)))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_leaf_paths(v, prefix + (i,)))
    else:
        out[prefix] = tree
    return out


_BATCH_AXES = ("layers", "experts")


def init_leaf(key: jax.Array, path: Tuple, spec: ParamSpec) -> jax.Array:
    """Materialize ONE parameter leaf. The leaf's key is derived from its tree
    path rather than traversal order, so initializing any SUBSET of leaves —
    e.g. one FSDP bucket at a time under jit with sharded outputs — is
    bit-identical to the full-tree init."""
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    # crc32, not hash(): str hashes are salted per process, and the weights
    # must depend on `key` alone
    k = jax.random.fold_in(key, zlib.crc32(repr(path).encode()) % (2**31))
    # a stacked-layers or experts axis is a batch of leaves, not fan-in
    dims = [n for n, a in zip(spec.shape, spec.axes) if a not in _BATCH_AXES]
    fan_in = dims[0] if len(dims) > 1 else max(dims[-1], 1)
    scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(fan_in)
    n = jax.random.normal(k, spec.shape, jnp.float32)
    # barrier: under jit XLA would merge this scale into normal()'s internal
    # sqrt(2) multiply (one rounding instead of two), so jitted per-bucket
    # init would drift a ulp from the eager full-tree init
    n = jax.lax.optimization_barrier(n)
    return (n * scale).astype(spec.dtype)


def init_from_specs(specs: PyTree, key: jax.Array) -> PyTree:
    """Materialize parameters. Each leaf gets an independent key derived from
    its tree path, so init is insensitive to traversal order."""
    flat = _leaf_paths(specs)
    leaves = {p: init_leaf(key, p, s) for p, s in flat.items()}

    def rebuild(tree, prefix=()):
        if isinstance(tree, dict):
            return {k: rebuild(tree[k], prefix + (k,)) for k in tree}
        if isinstance(tree, (list, tuple)):
            return type(tree)(rebuild(v, prefix + (i,)) for i, v in enumerate(tree))
        return leaves[prefix]

    return rebuild(specs)


def abstract_from_specs(specs: PyTree) -> PyTree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs,
        is_leaf=lambda s: isinstance(s, ParamSpec))


def axes_from_specs(specs: PyTree) -> PyTree:
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda s: isinstance(s, ParamSpec))


def layers_from_specs(specs: PyTree) -> PyTree:
    """Layer-provenance tree (same structure as the params): each leaf's
    forward depth, untagged specs defaulting to depth 0 (the input end, whose
    gradients complete last)."""
    return jax.tree.map(lambda s: 0 if s.layer is None else s.layer, specs,
                        is_leaf=lambda s: isinstance(s, ParamSpec))


def tag_layer(specs: PyTree, depth: int) -> PyTree:
    """Stamp `depth` as the layer provenance of every spec in the subtree."""
    import dataclasses

    return jax.tree.map(lambda s: dataclasses.replace(s, layer=depth), specs,
                        is_leaf=lambda s: isinstance(s, ParamSpec))


# ------------------------------------------------------------------- layers
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                                 # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(seq: int, dim: int) -> jax.Array:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(10000.0) / dim))
    emb = jnp.zeros((seq, dim), jnp.float32)
    emb = emb.at[:, 0::2].set(jnp.sin(pos * div))
    emb = emb.at[:, 1::2].set(jnp.cos(pos * div))
    return emb


# ---------------------------------------------------------------- dense MLP
def mlp_specs(d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    return {
        "gate": ParamSpec((d_model, d_ff), ("embed", "mlp"), dtype),
        "up": ParamSpec((d_model, d_ff), ("embed", "mlp"), dtype),
        "down": ParamSpec((d_ff, d_model), ("mlp", "embed"), dtype),
    }


def mlp_apply(p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """SwiGLU MLP with TP sharding constraints on the hidden activation."""
    h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    h = with_logical(h, ("batch", None, "mlp"))
    return h @ p["down"]
