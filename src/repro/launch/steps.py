"""Step builders shared by the dry-run, the trainer and the server.

A *cell* is (architecture x input shape). `build_cell` returns the jitted-able
step function plus abstract arg specs, logical axes and donation info — the
dry-run lowers it with ShapeDtypeStructs, the real drivers call it with
arrays. One code path for both is the point: the dry-run proves exactly what
production would run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint.elastic import shardings_for
from repro.config.base import ModelConfig, ParallelConfig
from repro.config.shapes import ShapeConfig
from repro.core.overlap import (FsdpLayout, accumulate_grads, fsdp_all_gather,
                                fsdp_layout, fsdp_stream, grad_sync_fsdp)
from repro.models.model import LanguageModel, ModelOptions, build_model, input_specs
from repro.optim import AdamWConfig, adamw_init, adamw_update, warmup_cosine
from repro.sharding.rules import ShardingContext, use_sharding

PyTree = Any


def explicit_sync_axes(parallel: ParallelConfig, mesh) -> Tuple[Tuple[str, ...], bool]:
    """(sync_axes, explicit): the DP axes present on `mesh`, and whether the
    explicit shard_map grad-sync schedules are faithful there. The explicit
    schedules treat params as replicated (or DP-sharded) inside shard_map,
    which is only sound when every non-DP mesh axis is trivial — a
    non-trivial TP axis must keep the GSPMD path."""
    if mesh is None:
        return (), False
    sync_axes = tuple(a for a in parallel.dp_axes if a in mesh.axis_names)
    explicit = bool(sync_axes) and all(
        mesh.shape[a] == 1 for a in mesh.axis_names if a not in sync_axes)
    return sync_axes, explicit


@dataclasses.dataclass
class Cell:
    """One lowered unit of work: fn(*args) with full sharding metadata."""

    name: str
    fn: Callable
    arg_specs: Tuple[PyTree, ...]       # ShapeDtypeStruct trees (positional)
    arg_axes: Tuple[PyTree, ...]        # logical-axes trees (same structure)
    donate_argnums: Tuple[int, ...]
    model: LanguageModel
    kind: str                           # train | prefill | decode

    @property
    def rules(self):
        from repro.sharding.rules import rules_for

        return rules_for(self.kind, self.model.cfg.d_model,
                         self.model.cfg.family)

    def context(self, mesh) -> ShardingContext:
        return ShardingContext(mesh, self.rules)

    def in_shardings(self, mesh) -> Tuple[PyTree, ...]:
        ctx = self.context(mesh)
        return tuple(shardings_for(s, a, mesh, ctx)
                     for s, a in zip(self.arg_specs, self.arg_axes))

    def lower(self, mesh, out_shardings=None):
        with use_sharding(mesh, self.rules), mesh:
            jitted = jax.jit(self.fn,
                             in_shardings=self.in_shardings(mesh),
                             out_shardings=out_shardings,
                             donate_argnums=self.donate_argnums)
            return jitted.lower(*self.arg_specs)


# --------------------------------------------------------------------- train
def make_train_step(model: LanguageModel, parallel: ParallelConfig,
                    opt_cfg: Optional[AdamWConfig] = None,
                    warmup_steps: int = 100, total_steps: int = 10_000
                    ) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    Gradient reduction over the DP axes is left to GSPMD (params sharded
    FSDP-style); parallel.overlap selects the explicit HDOT bucketed schedule
    when the step runs under shard_map-style manual axes (trainer benches).
    """
    opt_cfg = opt_cfg or AdamWConfig()
    accum = parallel.accum_steps
    # Layer-chunked optimizer update is available (adamw_update chunk_leading)
    # but measured WORSE on the XLA-CPU dry-run (+12 GB: while-loop outputs
    # don't alias donated inputs); the unchunked elementwise update fuses to
    # ~zero temp on the TPU target. Keep unchunked. (EXPERIMENTS §Perf it. 2)
    chunk_leading = 0
    p_axes = model.param_axes()

    def constrain_like_params(grads):
        """Anchor gradient shardings to the parameter placements. Without
        this, GSPMD replicates the (vocab, d_model) embedding/lm_head grads
        (scatter-add / final dot) — measured 8.4 GB/chip f32 buffers for
        llama3-405b (EXPERIMENTS §Perf iteration 1)."""
        from repro.sharding.rules import with_logical

        return jax.tree.map(
            lambda g, ax: with_logical(g, ax), grads, p_axes)

    def loss_and_grad(params, batch):
        loss, grads = jax.value_and_grad(model.train_loss)(params, batch)
        return loss, constrain_like_params(grads)

    def step_fn(params, opt_state, batch):
        loss, grads = accumulate_grads(loss_and_grad, params, batch, accum)
        lr = warmup_cosine(opt_state["step"], opt_cfg.lr, warmup_steps,
                           total_steps)
        params, opt_state, gnorm = adamw_update(grads, opt_state, params,
                                                opt_cfg, lr,
                                                chunk_leading=chunk_leading)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    return step_fn


# ------------------------------------------------------------ train (ZeRO-3)
def _require_explicit_mesh(parallel: ParallelConfig, mesh) -> Tuple[str, ...]:
    """sync_axes, or a loud error when the mesh cannot host the explicit
    ZeRO-3 step (a non-trivial TP axis would silently replicate under the
    flat-shard shard_map). Single source for the param_shard precondition."""
    sync_axes, explicit = explicit_sync_axes(parallel, mesh)
    if not explicit:
        raise ValueError(
            "param_shard=True needs the explicit-schedule step: a mesh whose "
            f"non-DP axes are all trivial (got mesh axes "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else None}, "
            f"dp_axes {parallel.dp_axes})")
    return sync_axes


def fsdp_layout_for(model: LanguageModel, parallel: ParallelConfig,
                    mesh) -> Tuple[FsdpLayout, Tuple[str, ...]]:
    """The bucket-wise flat-buffer layout of `model`'s params for ZeRO-3
    sharding over the mesh's DP axes (layer-boundary buckets when
    ``parallel.bucket_order == 'reverse_topo'``; one bucket PER layer when
    ``parallel.fsdp_streaming`` so each gather has a single consuming
    layer)."""
    sync_axes = _require_explicit_mesh(parallel, mesh)
    n_shards = 1
    for a in sync_axes:
        n_shards *= mesh.shape[a]
    order = "layer" if parallel.fsdp_streaming else parallel.bucket_order
    layers = (model.param_layers()
              if order in ("reverse_topo", "layer") else None)
    layout = fsdp_layout(model.abstract_params(), n_shards,
                         parallel.grad_buckets, layers=layers, order=order)
    return layout, sync_axes


def fsdp_init_state(model: LanguageModel, parallel: ParallelConfig, mesh,
                    rng) -> Tuple[Dict[str, jax.Array], PyTree, FsdpLayout]:
    """Materialize the ZeRO-3 trainer state: params and AdamW moments as
    bucket-wise flat buffers placed with ``P(dp_axes)`` shardings —
    per-device parameter/opt residency is 1/n_shards of the replicated
    step's. Returns (params_flat, opt_state, layout).

    Init is SHARDED per bucket: each flat buffer comes out of its own jitted
    init with ``out_shardings=P(dp_axes)``, so the full tree never
    materializes — transient per-device bytes stay within
    ``layout.shard_bytes()`` plus one bucket. Bit-identical to the old
    full-materialize init: every leaf's key derives from its tree path
    (``models.layers.init_leaf``), not from traversal order."""
    import functools

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models.layers import _leaf_paths, init_leaf

    layout, sync_axes = fsdp_layout_for(model, parallel, mesh)
    sharding = NamedSharding(mesh, P(sync_axes))
    paths = list(_leaf_paths(model.param_specs()).items())
    if len(paths) != layout.num_leaves:  # pragma: no cover - structural guard
        raise ValueError(f"param_specs has {len(paths)} leaves, layout packs "
                         f"{layout.num_leaves}")

    from repro.core.overlap import _pack_group

    def group_init(key, g):
        leaves = [None] * layout.num_leaves
        for i in g.leaf_idx:
            path, spec = paths[i]
            leaves[i] = init_leaf(key, path, spec)
        return _pack_group(leaves, g)

    def group_zeros(g):
        return jnp.zeros((g.padded,), jnp.float32)

    flat, m, v = {}, {}, {}
    with mesh:
        for g in layout.groups:
            flat[g.key] = jax.jit(functools.partial(group_init, g=g),
                                  out_shardings=sharding)(rng)
            zeros = jax.jit(functools.partial(group_zeros, g),
                            out_shardings=sharding)
            m[g.key], v[g.key] = zeros(), zeros()
    # the step counter is placed as the step's output is (replicated on the
    # mesh): an uncommitted scalar would key a second compile of step 2
    step = jax.device_put(jnp.zeros((), jnp.int32), NamedSharding(mesh, P()))
    opt = {"m": m, "v": v, "step": step}
    return flat, opt, layout


def make_fsdp_train_step(model: LanguageModel, parallel: ParallelConfig, mesh,
                         opt_cfg: Optional[AdamWConfig] = None,
                         warmup_steps: int = 100, total_steps: int = 10_000,
                         layout: Optional[FsdpLayout] = None) -> Callable:
    """(params_flat, opt_state, batch) -> (params_flat, opt_state, metrics):
    the FSDP (ZeRO-3) composition of the explicit HDOT grad-sync schedule.

    Inside shard_map over the DP axes: bucket-wise all-gather of the flat
    parameter shards in FORWARD order, loss/backward on the gathered params,
    then a bucket-wise reduce-scatter EMITTED reverse-topologically (the
    last-backward bucket's collective first, free to depart while earlier
    layers' backward computes). The AdamW update then runs OUTSIDE shard_map
    directly on the flat shards — elementwise math GSPMD keeps partitioned,
    so optimizer state never materializes unsharded.

    With ``parallel.fsdp_streaming`` the top-of-step gather-all is replaced
    by the streaming schedule: per-layer buckets are all-gathered inside
    each consuming layer's remat region (``train_loss_streamed``), freed
    after that layer's forward, and REGATHERED in reverse order by the
    backward — whose AD transpose emits the per-bucket reduce-scatters
    last-backward-first automatically. Peak live params drop from the full
    tree to shard + a ``fsdp_working_set``-bucket working set; losses,
    params and moments stay bit-identical to the gather-all step."""
    opt_cfg = opt_cfg or AdamWConfig()
    accum = parallel.accum_steps
    if layout is None:
        layout, sync_axes = fsdp_layout_for(model, parallel, mesh)
    else:
        sync_axes = _require_explicit_mesh(parallel, mesh)
    n_shards = layout.n_shards

    def loss_and_grad(params, batch):
        return jax.value_and_grad(model.train_loss)(params, batch)

    if parallel.fsdp_streaming:
        stream = fsdp_stream(layout, model.param_layers(), sync_axes)

        def streamed_loss_and_grad(pflat, batch):
            return jax.value_and_grad(model.train_loss_streamed)(
                pflat, batch, stream)

        def local(pflat, b):
            from repro.sharding.rules import no_sharding

            # manual region: logical sharding constraints must be inert
            with no_sharding():
                # gathers are emitted point-of-use inside the loss; AD
                # returns grads already reduce-scattered per bucket
                loss, gflat = accumulate_grads(streamed_loss_and_grad,
                                               pflat, b, accum)
            gflat = {k: v / n_shards for k, v in gflat.items()}
            return jax.lax.pmean(loss, sync_axes), gflat
    else:
        def local(pflat, b):
            from repro.sharding.rules import no_sharding

            # manual region: logical sharding constraints must be inert
            with no_sharding():
                params = fsdp_all_gather(pflat, layout, sync_axes)
                loss, g = accumulate_grads(loss_and_grad, params, b, accum)
                gflat = grad_sync_fsdp(g, layout, sync_axes)
            # psum_scatter of per-shard mean-grads -> global mean over shards
            gflat = {k: v / n_shards for k, v in gflat.items()}
            return jax.lax.pmean(loss, sync_axes), gflat

    def grads_fn(pflat, batch):
        from jax.sharding import PartitionSpec as P

        flat_specs = {k: P(sync_axes) for k in layout.keys}
        batch_specs = jax.tree.map(
            lambda x: P(sync_axes, *([None] * (x.ndim - 1))), batch)
        return jax.shard_map(
            local, mesh=mesh, in_specs=(flat_specs, batch_specs),
            out_specs=(P(), flat_specs), check_vma=False)(pflat, batch)

    def step_fn(pflat, opt_state, batch):
        loss, gflat = grads_fn(pflat, batch)
        lr = warmup_cosine(opt_state["step"], opt_cfg.lr, warmup_steps,
                           total_steps)
        pflat, opt_state, gnorm = adamw_update(gflat, opt_state, pflat,
                                               opt_cfg, lr)
        return pflat, opt_state, {"loss": loss, "grad_norm": gnorm, "lr": lr}

    return step_fn


# --------------------------------------------------------------------- serve
def make_prefill_step(model: LanguageModel) -> Callable:
    def prefill_fn(params, batch):
        return model.prefill(params, batch)

    return prefill_fn


def make_decode_step(model: LanguageModel) -> Callable:
    def decode_fn(params, caches, token, pos):
        logits, new_caches = model.decode_step(params, token, caches, pos)
        return logits, new_caches

    return decode_fn


# ---------------------------------------------------------------- cell build
def opt_state_specs(model: LanguageModel, moment_dtype=jnp.float32
                    ) -> Tuple[PyTree, PyTree]:
    """(abstract opt state, logical axes) matching adamw_init(params)."""
    p_abs = model.abstract_params()
    p_axes = model.param_axes()
    mom = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, moment_dtype),
                       p_abs)
    specs = {"m": mom, "v": mom,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    axes = {"m": p_axes, "v": p_axes, "step": ()}
    return specs, axes


def build_cell(cfg: ModelConfig, shape: ShapeConfig,
               options: Optional[ModelOptions] = None,
               parallel: Optional[ParallelConfig] = None,
               moment_dtype=jnp.float32) -> Cell:
    parallel = parallel or ParallelConfig()
    options = options or ModelOptions(
        attn_impl="blockwise" if shape.seq_len > 8192 else "dense",
        scan_layers=parallel.scan_layers, remat=parallel.remat,
        moe_a2a_chunks=parallel.moe_a2a_chunks)
    model = build_model(cfg, options)
    io = input_specs(cfg, shape, options)
    batch_specs, batch_axes = io["specs"], io["axes"]
    p_abs = model.abstract_params()
    p_axes = model.param_axes()

    if shape.kind == "train":
        fn = make_train_step(model, parallel)
        o_abs, o_axes = opt_state_specs(model, moment_dtype)
        return Cell(
            name=f"{cfg.name}:{shape.name}", fn=fn,
            arg_specs=(p_abs, o_abs, batch_specs),
            arg_axes=(p_axes, o_axes, batch_axes),
            donate_argnums=(0, 1), model=model, kind="train")

    if shape.kind == "prefill":
        fn = make_prefill_step(model)
        return Cell(
            name=f"{cfg.name}:{shape.name}", fn=fn,
            arg_specs=(p_abs, batch_specs),
            arg_axes=(p_axes, batch_axes),
            donate_argnums=(), model=model, kind="prefill")

    # decode: batch_specs = {'token', 'caches', 'pos'}
    fn = make_decode_step(model)
    return Cell(
        name=f"{cfg.name}:{shape.name}", fn=fn,
        arg_specs=(p_abs, batch_specs["caches"], batch_specs["token"],
                   batch_specs["pos"]),
        arg_axes=(p_axes, batch_axes["caches"], batch_axes["token"],
                  batch_axes["pos"]),
        donate_argnums=(1,), model=model, kind="decode")
