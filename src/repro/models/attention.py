"""Attention: GQA (+qk-norm, +sliding window), train/prefill/decode paths.

Implementations (``impl``):
  dense      -- full-score einsum attention (oracle; decode path; small shapes)
  blockwise  -- lax.scan over query chunks, memory-bounded (runnable lowering
                for long prefill; XLA buffer-reuses one chunk of scores)
  blockwise_unrolled -- python-loop chunks (analysis lowering: FLOPs of every
                chunk visible to cost_analysis; scan bodies are counted once)
  flash      -- Pallas TPU kernel (repro.kernels.flash_attention); interpret
                mode on CPU tests

All paths share the projection/rope/mask logic, so implementations are
interchangeable and cross-checked in tests.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config.base import ModelConfig
from repro.models.layers import ParamSpec, apply_rope, rms_norm
from repro.sharding.rules import with_logical

Cache = Dict[str, jax.Array]


# ---------------------------------------------------------------------- specs
def attention_specs(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    hd = cfg.resolved_head_dim
    s: Dict[str, ParamSpec] = {
        "wq": ParamSpec((cfg.d_model, cfg.num_heads, hd), ("embed", "heads", "head_dim"), dtype),
        "wk": ParamSpec((cfg.d_model, cfg.num_kv_heads, hd),
                        ("embed", "kv_heads", "head_dim"), dtype),
        "wv": ParamSpec((cfg.d_model, cfg.num_kv_heads, hd),
                        ("embed", "kv_heads", "head_dim"), dtype),
        "wo": ParamSpec((cfg.num_heads, hd, cfg.d_model), ("heads", "head_dim", "embed"),
                        dtype, scale=(cfg.num_heads * hd) ** -0.5),  # fan-in h*hd
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), (None,), jnp.float32, "ones")
        s["k_norm"] = ParamSpec((hd,), (None,), jnp.float32, "ones")
    return s


# ---------------------------------------------------------------- projections
def project_q(p, x, cfg: ModelConfig, positions) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    return with_logical(q, ("batch", None, "act_heads", None))


def project_kv(p, x, cfg: ModelConfig, positions) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    k = apply_rope(k, positions, cfg.rope_theta)
    k = with_logical(k, ("batch", None, "act_kv_heads", None))
    v = with_logical(v, ("batch", None, "act_kv_heads", None))
    return k, v


# ------------------------------------------------------------------ core sdpa
def _mask(q_pos, k_pos, causal: bool, window: Optional[int]) -> jax.Array:
    """(..., q, k) boolean mask. window counts the current token (SWA)."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    return m


def _sdpa_dense(q, k, v, q_pos, k_pos, causal, window, kv_valid=None) -> jax.Array:
    """q: (b,sq,hq,d); k,v: (b,sk,hkv,d). GQA via kv broadcast to full heads.

    Scores stay (b, hq, sq, sk) so the head dim is shardable over the TP axis
    even when hkv < mesh model size (the grouped (hkv, g, ...) layout forced
    score replication + involuntary SPMD remats — measured in the dry-run)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(d)
    # under tp_sp rules heads own the model axis (seq falls through to None);
    # under dp_sp rules heads replicate and the q-row dim carries it instead
    scores = with_logical(scores, ("batch", "act_heads", "seq", None))
    m = _mask(q_pos, k_pos, causal, window)[:, None]              # (b,1,sq,sk)
    if kv_valid is not None:
        m &= kv_valid[:, None, None, :]
    scores = jnp.where(m, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    out = with_logical(out, ("batch", "seq", "act_heads", None))
    return out.astype(q.dtype)


def _sdpa_blockwise(q, k, v, q_pos, k_pos, causal, window, chunk: int,
                    unrolled: bool) -> jax.Array:
    b, sq, hq, d = q.shape
    chunk = min(chunk, sq)
    if sq % chunk != 0:
        return _sdpa_dense(q, k, v, q_pos, k_pos, causal, window)
    n = sq // chunk

    def one(i):
        qs = lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        qp = lax.dynamic_slice_in_dim(q_pos, i * chunk, chunk, axis=-1)
        return _sdpa_dense(qs, k, v, qp, k_pos, causal, window)

    if unrolled:
        outs = [one(i) for i in range(n)]
        return jnp.concatenate(outs, axis=1)
    ys = lax.map(lambda i: one(i), jnp.arange(n))
    return jnp.moveaxis(ys, 0, 1).reshape(b, sq, hq, d)


def sdpa(q, k, v, q_pos, k_pos, causal=True, window=None, impl="dense",
         chunk: int = 1024, kv_valid=None) -> jax.Array:
    if impl == "dense":
        return _sdpa_dense(q, k, v, q_pos, k_pos, causal, window, kv_valid)
    if impl == "blockwise":
        return _sdpa_blockwise(q, k, v, q_pos, k_pos, causal, window, chunk, False)
    if impl == "blockwise_unrolled":
        return _sdpa_blockwise(q, k, v, q_pos, k_pos, causal, window, chunk, True)
    if impl == "flash":
        from repro.kernels.flash_attention import ops as flash_ops

        return flash_ops.flash_attention(q, k, v, causal=causal, window=window,
                                         q_offset=q_pos, k_offset=k_pos)
    raise ValueError(f"unknown attention impl {impl!r}")


# ---------------------------------------------------------------- full blocks
def self_attention(p, x, cfg: ModelConfig, positions, causal=True,
                   impl="dense", window=None) -> jax.Array:
    """Train/prefill self-attention over the full sequence."""
    q = project_q(p, x, cfg, positions)
    k, v = project_kv(p, x, cfg, positions)
    out = sdpa(q, k, v, positions, positions, causal=causal,
               window=window, impl=impl)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return with_logical(y, ("batch", "seq", None))


def make_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Cache:
    """Ring-buffer KV cache. For SWA archs max_len may be min(seq, window)."""
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        # absolute position stored in each ring slot (-1 = empty)
        "pos": jnp.full((max_len,), -1, jnp.int32),
    }


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.resolved_head_dim
    return {
        "k": ParamSpec((batch, max_len, cfg.num_kv_heads, hd),
                       ("batch", "kv_seq", "act_kv_heads", None), dtype, "zeros"),
        "v": ParamSpec((batch, max_len, cfg.num_kv_heads, hd),
                       ("batch", "kv_seq", "act_kv_heads", None), dtype, "zeros"),
        "pos": ParamSpec((max_len,), ("kv_seq",), jnp.int32, "zeros"),
    }


def prefill_attention(p, x, cfg: ModelConfig, positions, cache: Cache,
                      impl="dense", window=None) -> Tuple[jax.Array, Cache]:
    """Full-sequence attention that also fills the cache (assumes seq fits the
    ring; launcher sizes caches accordingly)."""
    q = project_q(p, x, cfg, positions)
    k, v = project_kv(p, x, cfg, positions)
    out = sdpa(q, k, v, positions, positions, causal=True, window=window, impl=impl)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = with_logical(y, ("batch", "seq", None))

    w = cache["k"].shape[1]
    s = k.shape[1]
    if s >= w:  # keep the last w entries, placed at their ring slots
        ks, vs = k[:, -w:], v[:, -w:]
        ps = positions[0, -w:] if positions.ndim > 1 else positions[-w:]
        # decode writes position p at slot p % w — prefill must agree, else
        # the next eviction removes the wrong token (caught by
        # test_prefill_decode_matches_full_forward[recurrentgemma-2b])
        slots = ps.astype(jnp.int32) % w
        new = {
            "k": jnp.zeros_like(cache["k"]).at[:, slots].set(
                ks.astype(cache["k"].dtype)),
            "v": jnp.zeros_like(cache["v"]).at[:, slots].set(
                vs.astype(cache["v"].dtype)),
            "pos": jnp.full((w,), -1, jnp.int32).at[slots].set(
                ps.astype(jnp.int32)),
        }
    else:
        pos1 = positions[0] if positions.ndim > 1 else positions
        new = {
            "k": lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1),
            "v": lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1),
            "pos": lax.dynamic_update_slice_in_dim(
                cache["pos"], pos1.astype(jnp.int32), 0, 0),
        }
    return y, new


def decode_attention(p, x, cfg: ModelConfig, cache: Cache, pos: jax.Array,
                     window=None) -> Tuple[jax.Array, Cache]:
    """One-token step against the ring cache. `pos` is a scalar int32 (same
    position for every sequence in the batch — the wave scheduler) or a
    per-slot (b,) vector (continuous batching: every slot decodes at its own
    position; the cache then carries a per-slot ``pos`` of shape (b, w)).

    Under a multi-chip sharding context the scalar-pos path dispatches to the
    shard_map flash-decode: the KV domain stays sequence-sharded, each chip
    computes a partial softmax over its subdomain and the results combine
    hierarchically (max + scaled sums) — the HDOT task-reduction pattern.
    Without it, GSPMD all-gathers the whole cache every token (measured
    1.02 GB/chip/layer for granite decode_32k — EXPERIMENTS §Perf cell C).
    The per-slot path is TP-sharded explicitly by models/decode_tp instead."""
    b = x.shape[0]
    per_slot = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_slot else jnp.broadcast_to(pos, (b, 1))
    q = project_q(p, x, cfg, positions)
    k, v = project_kv(p, x, cfg, positions)

    w = cache["k"].shape[1]
    from repro.sharding.rules import current_context, resolve_pspec

    ctx = current_context()
    kv_axes: Tuple[str, ...] = ()
    if ctx is not None:
        spec = resolve_pspec(cache["k"].shape,
                             ("batch", "kv_seq", "act_kv_heads", None), ctx)
        entry = spec[1] if len(spec) > 1 else None
        if entry is not None:
            kv_axes = entry if isinstance(entry, tuple) else (entry,)
    n_shards = 1
    for a in kv_axes:
        n_shards *= ctx.axis_size(a)
    if kv_axes and n_shards > 1 and w % n_shards == 0 and not per_slot:
        out, new_cache = _flash_decode_sharded(q, k, v, cache, pos, window,
                                               ctx, kv_axes)
    else:
        out, new_cache = _decode_dense(q, k, v, cache, pos, window)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = with_logical(y, ("batch", None, None))
    return y, new_cache


def _decode_dense(q, k, v, cache: Cache, pos, window) -> Tuple[jax.Array, Cache]:
    """Single-device reference decode path (also the oracle for the sharded
    flash-decode in tests). Scalar `pos` updates one shared ring slot; a
    per-slot (b,) `pos` scatters row-wise into a per-slot (b, w) ring."""
    b = q.shape[0]
    w = cache["k"].shape[1]
    if jnp.ndim(pos) == 1:
        # continuous batching: each slot writes its own ring position
        positions = pos[:, None]
        slot = (pos % w).astype(jnp.int32)
        rows = jnp.arange(b)
        ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
        cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
        cpos = cache["pos"].at[rows, slot].set(pos.astype(jnp.int32))
        k_pos = cpos                                            # (b, w)
        kv_valid = cpos >= 0
    else:
        positions = jnp.broadcast_to(pos, (b, 1))
        slot = pos % w
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, 1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, 1)
        cpos = lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.reshape(pos, (1,)).astype(jnp.int32), slot, 0)
        k_pos = jnp.broadcast_to(cpos, (b, w))
        kv_valid = jnp.broadcast_to(cpos >= 0, (b, w))
    out = _sdpa_dense(q, ck, cv, positions, k_pos, causal=True, window=window,
                      kv_valid=kv_valid)
    return out, {"k": ck, "v": cv, "pos": cpos}


def _flash_decode_sharded(q, k, v, cache: Cache, pos, window,
                          ctx, kv_axes: Tuple[str, ...] = ("model",)
                          ) -> Tuple[jax.Array, Cache]:
    """shard_map flash-decode over the seq-sharded ring cache.

    Per chip: local DUS (the writing chip is the slot owner), local partial
    softmax (m, sum exp, weighted V), then pmax/psum combine over `kv_axes`
    — per-layer wire is O(b*h*hd) instead of O(b*S*kv*hd)."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import resolve_pspec

    mesh = ctx.mesh
    axis = kv_axes if len(kv_axes) > 1 else kv_axes[0]
    n_shards = 1
    for a in kv_axes:
        n_shards *= ctx.axis_size(a)
    b, _, hq, hd = q.shape
    w = cache["k"].shape[1]
    chunk = w // n_shards
    hkv = cache["k"].shape[2]
    g = hq // hkv
    scale = 1.0 / math.sqrt(hd)

    batch_spec = resolve_pspec((b,), ("batch",), ctx)
    bax = batch_spec[0] if len(batch_spec) else None
    if isinstance(bax, tuple):  # drop axes the cache seq dim already uses
        bax = tuple(a for a in bax if a not in kv_axes) or None
    elif bax in kv_axes:
        bax = None

    def body(q, k_new, v_new, ck, cv, cpos, pos):
        # ck/cv: (b_loc, chunk, hkv, hd); cpos: (chunk,)
        idx = lax.axis_index(axis)
        slot = pos % w
        owner = slot // chunk == idx
        local_slot = jnp.where(owner, slot % chunk, 0)
        ck = jnp.where(
            owner,
            lax.dynamic_update_slice_in_dim(ck, k_new.astype(ck.dtype),
                                            local_slot, 1), ck)
        cv = jnp.where(
            owner,
            lax.dynamic_update_slice_in_dim(cv, v_new.astype(cv.dtype),
                                            local_slot, 1), cv)
        cpos = jnp.where(
            owner,
            lax.dynamic_update_slice_in_dim(
                cpos, jnp.reshape(pos, (1,)).astype(jnp.int32), local_slot, 0),
            cpos)

        kk = jnp.repeat(ck, g, axis=2) if g > 1 else ck      # (b,chunk,hq,hd)
        vv = jnp.repeat(cv, g, axis=2) if g > 1 else cv
        s = jnp.einsum("bqhd,bthd->bhqt", q.astype(jnp.float32),
                       kk.astype(jnp.float32)) * scale        # (b,h,1,chunk)
        valid = (cpos >= 0) & (cpos <= pos)
        if window is not None:
            valid &= cpos > pos - window
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        m_loc = jnp.max(s, axis=-1, keepdims=True)            # (b,h,1,1)
        m_glob = lax.pmax(m_loc, axis)
        # all-masked shards: exp(-inf - finite) = 0 contribution
        p_ = jnp.exp(s - m_glob)
        p_ = jnp.where(valid[None, None, None, :], p_, 0.0)
        den = lax.psum(jnp.sum(p_, axis=-1), axis)            # (b,h,1)
        num = lax.psum(jnp.einsum("bhqt,bthd->bqhd", p_,
                                  vv.astype(jnp.float32)), axis)
        out = num / jnp.maximum(den, 1e-30)[:, :, :, None].swapaxes(1, 2)
        return out.astype(q.dtype), ck, cv, cpos

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bax), P(bax), P(bax), P(bax, axis), P(bax, axis),
                  P(axis), P()),
        out_specs=(P(bax), P(bax, axis), P(bax, axis), P(axis)))
    out, ck, cv, cpos = fn(q, k, v, cache["k"], cache["v"], cache["pos"], pos)
    return out, {"k": ck, "v": cv, "pos": cpos}


# ------------------------------------------------------------ cross-attention
def cross_attention_specs(cfg: ModelConfig, dtype=jnp.bfloat16) -> Dict[str, ParamSpec]:
    return attention_specs(cfg, dtype)


def cross_attention(p, x, enc_kv: Tuple[jax.Array, jax.Array], cfg: ModelConfig) -> jax.Array:
    """Decoder->encoder attention; enc k/v precomputed once at prefill."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])   # no rope on cross-attn
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    k, v = enc_kv
    t = k.shape[1]
    k_pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    out = _sdpa_dense(q, k, v, positions, k_pos, causal=False, window=None)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def encode_cross_kv(p, enc_out: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    if cfg.qk_norm:
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return k, v
