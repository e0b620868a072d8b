"""Mixture-of-Experts with grouped capacity dispatch (GShard-style groups).

Tokens are processed in GROUPS (one sequence per group) so that all dispatch
bookkeeping (top-k, rank-within-expert cumsum, scatter/gather) happens along
un-sharded dims — groups stay sharded over the DP axes, experts over the TP
axis, and GSPMD inserts the group->expert all-to-all. Expert FLOPs equal the
*active* compute (2*E*C*D*F with E*C ~= tokens*top_k*capacity_factor), so
roofline numbers reflect true MoE economics rather than dense-all-experts.

HDOT view: the expert-capacity buffers are task-level subdomains of the token
domain; the dispatch collective is a per-subdomain communication task that the
scheduler can overlap with the attention compute of neighboring microbatches.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models.layers import ParamSpec
from repro.sharding.rules import with_logical


def moe_specs(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    m = cfg.moe
    if m is None:
        raise ValueError(
            f"moe_specs: config {cfg.name!r} (family={cfg.family!r}) has no "
            f"MoEConfig — only family='moe' configs carry cfg.moe")
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    return {
        "router": ParamSpec((d, e), ("embed", None), jnp.float32),
        "gate": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), dtype),
        "up": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp"), dtype),
        "down": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed"), dtype),
    }


def capacity(tokens_per_group: int, num_experts: int, top_k: int,
             capacity_factor: float) -> int:
    return max(top_k, int(math.ceil(tokens_per_group * top_k / num_experts
                                    * capacity_factor)))


def _dispatch_tables(assign: jax.Array, E: int, C: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """assign: (G, T, K) expert ids. Returns
       gather_ids (G, E, C)  token index feeding each expert slot (T = pad),
       slot_rank  (G, T, K)  rank of each assignment within its expert,
       keep       (G, T, K)  capacity mask."""
    G, T, K = assign.shape
    onehot = jax.nn.one_hot(assign.reshape(G, T * K), E, dtype=jnp.int32)   # (G,TK,E)
    ranks = jnp.cumsum(onehot, axis=1) - onehot
    rank = jnp.sum(ranks * onehot, axis=-1)                                  # (G,TK)
    eid = assign.reshape(G, T * K)
    keep = rank < C
    slot = jnp.where(keep, eid * C + rank, E * C)
    token = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K)).reshape(T * K)
    token = jnp.broadcast_to(token, (G, T * K))
    buf = jnp.full((G, E * C + 1), T, jnp.int32)
    buf = buf.at[jnp.arange(G)[:, None], slot].set(token)
    gather_ids = buf[:, :E * C].reshape(G, E, C)
    return gather_ids, rank.reshape(G, T, K), keep.reshape(G, T, K)


def moe_apply(p, x: jax.Array, cfg: ModelConfig,
              a2a_chunks: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Dispatches to the expert-parallel a2a path when the mesh
    shards experts (E divisible by the model axis); otherwise the dense
    capacity-dispatch below. `a2a_chunks` is the EP dispatch/combine
    over-decomposition degree Q (core.a2a_scan; 1 = monolithic).
    Returns (output, aux load-balancing loss)."""
    m = cfg.moe
    if m is None:
        raise ValueError(
            f"moe_apply: config {cfg.name!r} (family={cfg.family!r}) has no "
            f"MoEConfig — only family='moe' configs carry cfg.moe")
    from repro.sharding.rules import current_context

    ctx = current_context()
    if ctx is not None:
        n = ctx.axis_size("model")
        if n > 1 and m.num_experts % n == 0:
            if x.shape[1] % n == 0:
                return moe_apply_ep(p, x, cfg, ctx, a2a_chunks=a2a_chunks)
            if x.shape[1] == 1 and x.shape[0] % n == 0:
                # decode: a single token per sequence — the BATCH is the
                # token domain; swap it into the seq slot so the same EP
                # dispatch applies (measured: qwen3-moe decode_32k collective
                # bytes, EXPERIMENTS §Perf cell-B addendum)
                y, aux = moe_apply_ep(p, x.swapaxes(0, 1), cfg, ctx,
                                      tokens_on_batch=True,
                                      a2a_chunks=a2a_chunks)
                return y.swapaxes(0, 1), aux
    return moe_apply_dense(p, x, cfg)


def moe_apply_dense(p, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """GSPMD capacity dispatch — groups are sequences (G=B, T=S). The
    reference semantics; also the path for expert counts the mesh cannot
    shard (mixtral's 8 experts on a 16-wide model axis -> expert-TP)."""
    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    C = capacity(S, E, K, m.capacity_factor)

    logits = x.astype(jnp.float32) @ p["router"]                  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, assign = jax.lax.top_k(probs, K)                     # (B,S,K)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # aux loss (Switch/GShard): E * sum_e f_e * p_e
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(assign, E), axis=2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(f_e * p_e) * m.router_aux_loss_coef

    gather_ids, rank, keep = _dispatch_tables(assign, E, C)

    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, D), x.dtype)], axis=1)      # T = zero row
    xe = jnp.take_along_axis(x_pad[:, :, None, :],
                             gather_ids.reshape(B, E * C)[:, :, None, None], axis=1)
    xe = xe.reshape(B, E, C, D)
    xe = with_logical(xe, ("batch", "experts", None, None))

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, p["gate"]))
    h = h * jnp.einsum("becd,edf->becf", xe, p["up"])
    h = with_logical(h, ("batch", "experts", None, "expert_mlp"))
    ye = jnp.einsum("becf,efd->becd", h, p["down"])
    ye = with_logical(ye, ("batch", "experts", None, None))

    # combine: y[g,t] = sum_k keep * w_k * ye[g, e_k, rank_k]
    ye_flat = ye.reshape(B, E * C, D)
    ye_flat = jnp.concatenate([ye_flat, jnp.zeros((B, 1, D), ye.dtype)], axis=1)
    slot = jnp.where(keep, assign * C + rank, E * C)              # (B,S,K)
    picked = jnp.take_along_axis(ye_flat[:, :, None, :],
                                 slot.reshape(B, S * K)[:, :, None, None], axis=1)
    picked = picked.reshape(B, S, K, D)
    w = (weights * keep).astype(picked.dtype)[..., None]
    y = jnp.sum(picked * w, axis=2)
    return y.astype(x.dtype), aux


# ------------------------------------------------------------ expert parallel
def moe_apply_ep(p, x: jax.Array, cfg: ModelConfig, ctx,
                 tokens_on_batch: bool = False,
                 a2a_chunks: int = 1) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert parallelism (§Perf cell B): tokens stay seq-sharded,
    experts stay model-sharded, and the ONLY cross-chip traffic is the
    all-to-all of capacity-bucketed tokens (there and back) — chunked into
    `a2a_chunks` capacity slices by `core.a2a_scan` so slice k+1's dispatch
    and slice k-1's combine overlap slice k's expert FFN.

    HDOT structure: the per-chip dispatch reuses the SAME `_dispatch_tables`
    scheme the dense path uses globally — the process-level partition applied
    one level down, exactly the paper's hierarchical reuse. Without this,
    GSPMD lowers the cross-shard combine gather to replicated (B, S*K, D)
    all-reduces (measured 21 GB/chip/layer for qwen3-moe train_4k)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.a2a_scan import a2a_scan
    from repro.sharding.rules import resolve_pspec

    m = cfg.moe
    B, S, D = x.shape
    E, K = m.num_experts, m.top_k
    n = ctx.axis_size("model")
    if E % n != 0:
        raise ValueError(
            f"moe_apply_ep: num_experts={E} is not divisible by the model "
            f"axis size {n} ({cfg.name!r}); EP shards experts over 'model' — "
            f"use the dense/expert-TP path for this mesh")
    E_loc = E // n
    if x.shape[1] % n != 0:
        token_dim = "batch" if tokens_on_batch else "seq"
        raise ValueError(
            f"moe_apply_ep: token dim ({token_dim}={x.shape[1]}) is not "
            f"divisible by the model axis size {n} ({cfg.name!r}); the EP "
            f"dispatch seq-shards tokens over 'model'")
    # per-shard capacity, sized to the LOCAL token count (dim 1 is sharded
    # over exactly the model axis in both the train and decode layouts) —
    # computed here, outside the shard_map body, so a bad Q fails loudly at
    # trace time instead of deep inside a reshape
    C = capacity(x.shape[1] // n, E, K, m.capacity_factor)
    if a2a_chunks < 1 or C % a2a_chunks != 0:
        raise ValueError(
            f"moe_apply_ep: a2a_chunks={a2a_chunks} must be >=1 and divide "
            f"the expert capacity C={C} (tokens/shard={x.shape[1] // n}, "
            f"num_experts={E}, top_k={K}, "
            f"capacity_factor={m.capacity_factor}, {cfg.name!r})")

    # router in GSPMD-land (weights may be FSDP-sharded over data)
    logits = x.astype(jnp.float32) @ p["router"]                  # (B,S,E)

    if tokens_on_batch:
        # x arrived swapped: dim0 is a single decode step, dim1 the batch.
        # The batch/token dim shards over model (+pod if present).
        bax = None
    else:
        logits = with_logical(logits, ("batch", "seq", None))
        bspec = resolve_pspec((B,), ("batch",), ctx)
        bax = bspec[0] if len(bspec) else None
        if isinstance(bax, tuple) and "model" in bax:
            bax = tuple(a for a in bax if a != "model") or None
        elif bax == "model":
            bax = None

    def body(x, logits, gate, up, down):
        # x: (B_loc, S_loc, D); gate/up/down: (E_loc, ...); logits (B_loc,S_loc,E)
        B_loc, S_loc, _ = x.shape
        probs = jax.nn.softmax(logits, axis=-1)
        weights, assign = jax.lax.top_k(probs, K)                 # (B_loc,S_loc,K)
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

        f_e = jnp.mean(jnp.sum(jax.nn.one_hot(assign, E), axis=2), axis=(0, 1))
        p_e = jnp.mean(probs, axis=(0, 1))
        f_e = jax.lax.pmean(f_e, "model")
        p_e = jax.lax.pmean(p_e, "model")
        if bax is not None:
            f_e = jax.lax.pmean(f_e, bax)
            p_e = jax.lax.pmean(p_e, bax)
        aux = E * jnp.sum(f_e * p_e) * m.router_aux_loss_coef

        # task-level dispatch, per chip — same scheme as the dense path,
        # capacity C closed over from the trace-time validation above
        gather_ids, rank, keep = _dispatch_tables(assign, E, C)
        x_pad = jnp.concatenate([x, jnp.zeros((B_loc, 1, D), x.dtype)], axis=1)
        xe = jnp.take_along_axis(
            x_pad[:, :, None, :],
            gather_ids.reshape(B_loc, E * C)[:, :, None, None], axis=1)
        xe = xe.reshape(B_loc, E, C, D)

        # process-level dispatch: a2a the expert-bucketed slots to the owners,
        # over-decomposed along the capacity dim — slice k+1's dispatch and
        # slice k-1's combine ride under slice k's FFN (a2a_chunks=1 emits
        # exactly the old monolithic two-a2a program)
        xs = xe.reshape(B_loc, n, E_loc, C, D)
        xs = jnp.moveaxis(xs, 1, 0)                               # (n, B_loc, E_loc, C, D)

        def ffn(xr, _k):
            # expert FFN over one received capacity slice (flops == active
            # tokens); einsums contract only d/f, never the sliced C dim,
            # so chunking is value-preserving
            Cq = xr.shape[3]
            xf = jnp.moveaxis(xr, 2, 0).reshape(E_loc, n * B_loc * Cq, D)
            h = jax.nn.silu(jnp.einsum("etd,edf->etf", xf, gate))
            h = h * jnp.einsum("etd,edf->etf", xf, up)
            yf = jnp.einsum("etf,efd->etd", h, down)
            # return trip layout (paper Code 11: weighted per-slot partials)
            return jnp.moveaxis(yf.reshape(E_loc, n, B_loc, Cq, D), 0, 2)

        ys = a2a_scan(xs, ffn, "model", chunks=a2a_chunks, dim=3)
        ye = jnp.moveaxis(ys, 0, 1).reshape(B_loc, E * C, D)
        ye = jnp.concatenate([ye, jnp.zeros((B_loc, 1, D), ye.dtype)], axis=1)
        slot = jnp.where(keep, assign * C + rank, E * C)
        picked = jnp.take_along_axis(
            ye[:, :, None, :],
            slot.reshape(B_loc, S_loc * K)[:, :, None, None], axis=1)
        picked = picked.reshape(B_loc, S_loc, K, D)
        w = (weights * keep).astype(picked.dtype)[..., None]
        y = jnp.sum(picked * w, axis=2)
        return y.astype(x.dtype), aux

    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(bax, "model", None), P(bax, "model", None),
                  P("model"), P("model"), P("model")),
        out_specs=(P(bax, "model", None), P()),
        check_vma=False)
    y, aux = fn(x, logits, p["gate"], p["up"], p["down"])
    return y, aux
