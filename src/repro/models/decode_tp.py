"""TP-sharded continuous-batching decode step on the HDOT collective matmuls.

One decode token per slot is tiny compute over large weights — the classic
latency-critical TP cell. GSPMD would emit two-phase all-gather / psum_scatter
walls around every projection; here the step is an explicit shard_map over a
("data", "model") mesh and every projection/FFN matmul rides
`ag_matmul_hdot` / `matmul_rs_hdot` (core.collective_matmul), so each ring
hop's ppermute travels under the previous chunk's matmul — the paper's
communication-task overlap, structurally checked by the `lm_decode_tp` lint
target (NO-OVERLAP-WINDOW at zero exposed collectives + exact PAIR-COUNT).

Layout per TP rank (Megatron + sequence parallelism over the SLOT dim):
  x_sp (slots_loc/tp, d)  --ag-ring-->  fused QKV (slots_loc, heads_loc)
  GQA attention fully local on the kv-head-sharded slot caches
  out --rs-ring--> x_sp;  same ag/rs pair for the fused gate|up / down MLP;
  one final ag ring into the replicated unembedding = full logits per rank.
Rings per step: 4 * num_layers + 1. The "data" axis is pure slot parallelism
(no cross-data communication at all).

Cache writes use per-row unrolled `lax.dynamic_update_slice` rather than a
vectorized scatter: HLO `scatter` counts as compute for the lint's overlap
windows, DUS does not — the bookkeeping must not be what hides a collective.

`build_decode_step(model, mesh)` returns a drop-in for
`BatchServer(decode_step_fn=...)`; greedy outputs are token-exact against the
single-device oracle (tests/test_decode_tp.py).
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.config.base import ModelConfig
from repro.core import collective_matmul as cm
from repro.models.attention import _sdpa_dense
from repro.models.layers import apply_rope, rms_norm
from repro.models.model import LanguageModel

PyTree = Any


def expected_permute_total(cfg: ModelConfig, slots: int, dp: int, tp: int,
                           chunks: Optional[int] = None) -> int:
    """PAIR-COUNT expectation for one decode step: (4L + 1) hdot rings
    (QKV-ag, wo-rs, gate|up-ag, down-rs per layer, plus the unembed ag),
    each `ring_permute_count` ppermutes — derived from the same
    `_ring_pieces` split the runtime unrolls."""
    s_sp = slots // dp // tp
    return (4 * cfg.num_layers + 1) * cm.ring_permute_count(
        s_sp, tp, chunks=chunks)


def build_decode_step(model: LanguageModel, mesh,
                      data_axis: str = "data", model_axis: str = "model",
                      mode: str = "hdot", chunks: Optional[int] = None):
    """Returns step(params, token (b,1), caches, pos (b,)) -> (logits, caches)
    with the BatchServer continuous-decode calling convention (per-slot pos,
    per-slot cache["pos"] rings). `mode="two_phase"` swaps every ring for the
    serial all_gather/psum_scatter reference (the broken lint fixture)."""
    cfg = model.cfg
    if cfg.family not in ("dense",):
        raise ValueError(
            f"TP decode cell supports the dense family, got {cfg.family!r}")
    dp = mesh.shape[data_axis]
    tp = mesh.shape[model_axis]
    hd = cfg.resolved_head_dim
    if cfg.num_heads % tp or cfg.num_kv_heads % tp:
        raise ValueError(
            f"heads ({cfg.num_heads} q / {cfg.num_kv_heads} kv) must divide "
            f"over the {tp}-way {model_axis!r} axis")
    if cfg.d_ff % tp:
        raise ValueError(f"d_ff {cfg.d_ff} must divide over tp={tp}")
    hq_loc = cfg.num_heads // tp
    hkv_loc = cfg.num_kv_heads // tp
    f_loc = cfg.d_ff // tp
    d = cfg.d_model
    scanned = model.opt.scan_layers

    def _layer(pl, x_sp, cache_l, pos, idx):
        b_loc = pos.shape[0]
        ck, cv, cpos = cache_l["k"], cache_l["v"], cache_l["pos"]
        w = ck.shape[1]
        h = rms_norm(x_sp, pl["norm1"], cfg.norm_eps)
        ap = pl["attn"]
        wq = lax.dynamic_slice_in_dim(ap["wq"], idx * hq_loc, hq_loc, 1)
        wk = lax.dynamic_slice_in_dim(ap["wk"], idx * hkv_loc, hkv_loc, 1)
        wv = lax.dynamic_slice_in_dim(ap["wv"], idx * hkv_loc, hkv_loc, 1)
        wqkv = jnp.concatenate([wq.reshape(d, hq_loc * hd),
                                wk.reshape(d, hkv_loc * hd),
                                wv.reshape(d, hkv_loc * hd)], axis=1)
        qkv = cm.ag_matmul(h, wqkv, model_axis, mode, chunks)  # (b_loc, ...)
        q = qkv[:, :hq_loc * hd].reshape(b_loc, 1, hq_loc, hd)
        k = qkv[:, hq_loc * hd:(hq_loc + hkv_loc) * hd
                ].reshape(b_loc, 1, hkv_loc, hd)
        v = qkv[:, (hq_loc + hkv_loc) * hd:].reshape(b_loc, 1, hkv_loc, hd)
        if cfg.qk_norm:
            q = rms_norm(q, ap["q_norm"], cfg.norm_eps)
            k = rms_norm(k, ap["k_norm"], cfg.norm_eps)
        positions = pos[:, None]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        # per-row unrolled ring write (see module docstring: DUS, not scatter)
        for i in range(b_loc):
            sl = pos[i] % w
            ck = lax.dynamic_update_slice(ck, k[i:i + 1].astype(ck.dtype),
                                          (i, sl, 0, 0))
            cv = lax.dynamic_update_slice(cv, v[i:i + 1].astype(cv.dtype),
                                          (i, sl, 0, 0))
            cpos = lax.dynamic_update_slice(cpos, pos[i].reshape(1, 1),
                                            (i, sl))
        out = _sdpa_dense(q, ck, cv, positions, cpos, causal=True,
                          window=cfg.sliding_window, kv_valid=cpos >= 0)
        wo = lax.dynamic_slice_in_dim(ap["wo"], idx * hq_loc, hq_loc, 0)
        x_sp = x_sp + cm.matmul_rs(out.reshape(b_loc, hq_loc * hd),
                                   wo.reshape(hq_loc * hd, d),
                                   model_axis, mode, chunks)
        h2 = rms_norm(x_sp, pl["norm2"], cfg.norm_eps)
        mp = pl["mlp"]
        wg = lax.dynamic_slice_in_dim(mp["gate"], idx * f_loc, f_loc, 1)
        wu = lax.dynamic_slice_in_dim(mp["up"], idx * f_loc, f_loc, 1)
        gu = cm.ag_matmul(h2, jnp.concatenate([wg, wu], axis=1),
                          model_axis, mode, chunks)
        hm = jax.nn.silu(gu[:, :f_loc]) * gu[:, f_loc:]
        wd = lax.dynamic_slice_in_dim(mp["down"], idx * f_loc, f_loc, 0)
        x_sp = x_sp + cm.matmul_rs(hm, wd, model_axis, mode, chunks)
        return x_sp, {"k": ck, "v": cv, "pos": cpos}

    def cell(params, token, caches, pos):
        idx = lax.axis_index(model_axis)
        b_loc = token.shape[0]
        b_sp = b_loc // tp
        pos = pos.astype(jnp.int32)
        tok_sp = lax.dynamic_slice_in_dim(token[:, 0], idx * b_sp, b_sp, 0)
        x_sp = (jnp.take(params["embed"], tok_sp, axis=0)
                * jnp.asarray(d ** 0.5, model.opt.dtype))
        new_layers = []
        for l in range(cfg.num_layers):
            if scanned:
                pl = jax.tree.map(lambda a: a[l], params["layers"])
                cl = {k_: caches[k_][l] for k_ in ("k", "v", "pos")}
            else:
                pl = params["layers"][l]
                cl = caches[l]
            x_sp, nl = _layer(pl, x_sp, cl, pos, idx)
            new_layers.append(nl)
        if scanned:
            new_caches = {k_: jnp.stack([nl[k_] for nl in new_layers])
                          for k_ in ("k", "v", "pos")}
        else:
            new_caches = new_layers
        xn = rms_norm(x_sp, params["final_norm"], cfg.norm_eps)
        wout = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = cm.ag_matmul(xn, wout, model_axis, mode, chunks)
        return logits.astype(jnp.float32)[:, None, :], new_caches

    def _cache_spec(path, leaf):
        last = getattr(path[-1], "key", None)
        nd = len(leaf.shape)
        if last == "pos":                       # (..., slots, w)
            return P(*(None,) * (nd - 2), data_axis, None)
        return P(*(None,) * (nd - 4), data_axis, None, model_axis, None)

    def step(params, token, caches, pos):
        b = token.shape[0]
        if b % (dp * tp):
            raise ValueError(
                f"slots ({b}) must divide over data*model = {dp * tp} for "
                f"the sequence-parallel ring schedule")
        cspecs = jax.tree_util.tree_map_with_path(_cache_spec, caches)
        f = jax.shard_map(
            cell, mesh=mesh,
            in_specs=(P(), P(data_axis, None), cspecs, P(data_axis)),
            out_specs=(P(data_axis, None, None), cspecs),
            check_vma=False)
        return f(params, token, caches, pos)

    return step
