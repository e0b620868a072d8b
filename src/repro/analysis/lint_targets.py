"""Canonical lowerings for the HLO schedule linter.

Each target lowers one of the repo's jitted programs — the stencil solvers,
the raw halo scans, the explicit grad-sync schedules, the lm train steps
(replicated-HDOT and FSDP) — to PRE-optimization HLO and pairs it with a
:class:`LintContext` whose expectations are **derived from the same code the
runtime uses** (``make_buckets`` / ``fsdp_layout`` element counts, the
schedule's pair-count arithmetic), so the linter cannot drift from the
implementation.

Lowering is abstract throughout (ShapeDtypeStructs, no parameters
materialized) — a full lm FSDP target lints in seconds on 8 fake CPU
devices (set ``--xla_force_host_platform_device_count`` before jax imports;
the CLI in ``hlo_lint`` does this).

``BROKEN`` holds the mutation fixtures: deliberately mis-scheduled variants
(unpeeled drain, tree bucket order, two-phase monolithic sync, lost
donation, double gather) that the test suite asserts DO trigger their rule.
They are buildable but excluded from ``all_targets()`` so CI lints only the
canonical set.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

from repro.analysis.rules import LintContext

# pair-count arithmetic per schedule (permute ops = 2 * pair-sets):
#   halo_scan_nd / heat2d : one fwd+bwd pair per axis per step, drain peeled
#   rk3                : 3 stages/step, fill + peeled final stage -> 3*steps
#   hpccg              : one exchange chain per iter, fill + iters-1
PERMUTES_HALO = lambda axes, steps: 2 * axes * steps
PERMUTES_RK3 = lambda axes, steps: 2 * axes * 3 * steps
PERMUTES_HPCCG = lambda axes, iters: 2 * axes * iters
#   moe EP a2a_scan   : dispatch + combine per capacity slice (2Q) in the
#                       forward, and 2Q again in the backward (a2a is its
#                       own transpose)
A2AS_MOE = lambda chunks: 4 * chunks

_HLO_DTYPE = {"float32": "f32", "float64": "f64", "float16": "f16",
              "bfloat16": "bf16", "int32": "s32", "int64": "s64",
              "int8": "s8", "uint8": "u8", "uint32": "u32", "bool": "pred",
              "float8_e4m3fn": "f8e4m3fn", "float8_e5m2": "f8e5m2"}


def hlo_dtype(np_dtype) -> str:
    import numpy as np

    return _HLO_DTYPE.get(np.dtype(np_dtype).name, np.dtype(np_dtype).name)


@dataclass
class Target:
    name: str
    hlo_text: str
    ctx: LintContext


TARGETS: Dict[str, Callable[[], Target]] = {}
BROKEN: Dict[str, Callable[[], Target]] = {}


def _register(name: str, registry: Dict):
    def deco(fn):
        registry[name] = fn
        fn.__lint_name__ = name
        return fn
    return deco


def target(name: str):
    return _register(name, TARGETS)


def broken(name: str):
    return _register(name, BROKEN)


def all_targets() -> List[str]:
    return list(TARGETS)


def describe() -> List[Tuple[str, str]]:
    return [(n, (fn.__doc__ or "").strip().splitlines()[0])
            for n, fn in TARGETS.items()]


def build(name: str) -> Target:
    fn = TARGETS.get(name) or BROKEN.get(name)
    if fn is None:
        raise KeyError(f"unknown lint target {name!r}; known: "
                       f"{', '.join([*TARGETS, *BROKEN])}")
    return fn()


def _pre_opt_text(jitted, *specs) -> str:
    return jitted.lower(*specs).compiler_ir(dialect="hlo").as_hlo_text()


# ----------------------------------------------------------- raw halo scans
def _halo_jit(ndim: int, steps: int, peel: bool, donate: bool = True):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.halo import halo_scan_nd
    from repro.launch.mesh import make_grid_mesh, make_mesh

    donate_argnums = (0,) if donate else ()
    if ndim == 1:
        mesh = make_mesh((4,), ("data",))
        avg3 = lambda p: (p[:-2] + p[1:-1] + p[2:]) / 3.0
        f = jax.shard_map(
            lambda x: halo_scan_nd(x, avg3, (("data", 0),), 1, steps,
                                   periodic=True, subdomains=(4,), peel=peel,
                                   unroll=steps)[0],
            mesh=mesh, in_specs=(P("data"),), out_specs=P("data"))
        spec = jax.ShapeDtypeStruct((16, 4), jnp.float32)
    elif ndim == 2:
        mesh = make_grid_mesh(2, 2)
        star = lambda p: (p[1:-1, 1:-1] + p[:-2, 1:-1] + p[2:, 1:-1]
                          + p[1:-1, :-2] + p[1:-1, 2:]) / 5.0
        f = jax.shard_map(
            lambda x: halo_scan_nd(x, star, (("rows", 0), ("cols", 1)), 1,
                                   steps, periodic=True, subdomains=(2, 2),
                                   peel=peel, unroll=steps)[0],
            mesh=mesh, in_specs=(P("rows", "cols"),),
            out_specs=P("rows", "cols"))
        spec = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    else:
        mesh = make_grid_mesh(2, 2, 2)
        axes = ("planes", "rows", "cols")
        star3 = lambda p: (p[1:-1, 1:-1, 1:-1] + p[:-2, 1:-1, 1:-1]
                           + p[2:, 1:-1, 1:-1] + p[1:-1, :-2, 1:-1]
                           + p[1:-1, 2:, 1:-1] + p[1:-1, 1:-1, :-2]
                           + p[1:-1, 1:-1, 2:]) / 7.0
        f = jax.shard_map(
            lambda x: halo_scan_nd(x, star3, tuple(zip(axes, (0, 1, 2))), 1,
                                   steps, periodic=True, peel=peel,
                                   unroll=steps)[0],
            mesh=mesh, in_specs=(P(*axes),), out_specs=P(*axes))
        spec = jax.ShapeDtypeStruct((8, 8, 8), jnp.float32)
    return jax.jit(f, donate_argnums=donate_argnums), spec


def _halo_target(name: str, ndim: int) -> Target:
    steps = 2
    jitted, spec = _halo_jit(ndim, steps, peel=True)
    ctx = LintContext(target=name,
                      expected_permute_total=PERMUTES_HALO(ndim, steps),
                      expect_donation=True)
    return Target(name, _pre_opt_text(jitted, spec), ctx)


@target("halo1d")
def _halo1d() -> Target:
    """halo_scan_nd, 1-D ring of 4, steps=2 unrolled+peeled, donated input."""
    return _halo_target("halo1d", 1)


@target("halo2d")
def _halo2d() -> Target:
    """halo_scan_nd on a 2x2 mesh, steps=2 unrolled+peeled, donated input."""
    return _halo_target("halo2d", 2)


@target("halo3d")
def _halo3d() -> Target:
    """halo_scan_nd on a 2x2x2 mesh, steps=2 unrolled+peeled, donated."""
    return _halo_target("halo3d", 3)


# --------------------------------------------------------------- solvers
@target("heat2d_1d")
def _heat2d_1d() -> Target:
    """heat2d Jacobi sweeps, 1-D slab decomposition over 4 devices."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _heat2d_solver
    from repro.launch.mesh import make_mesh

    f = _heat2d_solver(make_mesh((4,), ("data",)), ("data",), 2, "hdot", 4)
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    return Target("heat2d_1d", txt,
                  LintContext(target="heat2d_1d",
                              expected_permute_total=PERMUTES_HALO(1, 2)))


@target("heat2d_2d")
def _heat2d_2d() -> Target:
    """heat2d with true 2-D (rows x cols) block decomposition on 2x2."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _heat2d_solver
    from repro.launch.mesh import make_grid_mesh

    f = _heat2d_solver(make_grid_mesh(2, 2), ("rows", "cols"), 2, "hdot",
                       (2, 2))
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    return Target("heat2d_2d", txt,
                  LintContext(target="heat2d_2d",
                              expected_permute_total=PERMUTES_HALO(2, 2)))


@target("heat2d_weighted")
def _heat2d_weighted() -> Target:
    """heat2d hdot with a measured-cost WEIGHTED (uneven) interior re-cut on
    a 2x2 mesh: the dynamic load-balancing lowering. The face partition — and
    thus the ppermute schedule — must be identical to the uniform cut (same
    pair count, zero exposed collectives); only the interior chunk grid is
    uneven (local 16x18 block, interior 14x16 cut (5,9) x (7,9))."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _heat2d_solver
    from repro.launch.mesh import make_grid_mesh

    f = _heat2d_solver(make_grid_mesh(2, 2), ("rows", "cols"), 2, "hdot",
                       (2, 2), ((5, 9), (7, 9)))
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((32, 36), jnp.float32))
    return Target("heat2d_weighted", txt,
                  LintContext(target="heat2d_weighted",
                              expected_permute_total=PERMUTES_HALO(2, 2)))


@target("rk3_1d")
def _rk3_1d() -> Target:
    """RK3 compressible Euler (LLF-split WENO5 fluxes, 5-component state),
    z-slab decomposition over 4 devices, steps=2."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _rk3_solver
    from repro.launch.mesh import make_mesh

    # global dim 3 (z) = 64 so the local shard keeps >= 4 * width cells (the
    # pipelined stage-carried path; smaller shards take the per-step fallback)
    f = _rk3_solver(make_mesh((4,), ("data",)), ("data",), 2, (1.0,) * 3,
                    "hdot")
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((5, 12, 16, 64), jnp.float32))
    return Target("rk3_1d", txt,
                  LintContext(target="rk3_1d",
                              expected_permute_total=PERMUTES_RK3(1, 2)))


@target("rk3_2d")
def _rk3_2d() -> Target:
    """RK3 compressible Euler on a (y, z) 2x2 grid mesh, stage-carried halos
    of all five components on both axes."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _rk3_solver
    from repro.launch.mesh import make_grid_mesh

    f = _rk3_solver(make_grid_mesh(2, 2), ("rows", "cols"), 2, (1.0,) * 3,
                    "hdot")
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((5, 12, 32, 32), jnp.float32))
    return Target("rk3_2d", txt,
                  LintContext(target="rk3_2d",
                              expected_permute_total=PERMUTES_RK3(2, 2)))


@target("hpccg_1d")
def _hpccg_1d() -> Target:
    """HPCCG CG iterations, 1-D decomposition over 4 devices, iters=2."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _hpccg_solver
    from repro.launch.mesh import make_mesh

    f = _hpccg_solver(make_mesh((4,), ("data",)), ("data",), 2, "hdot", 4)
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((12, 20, 20), jnp.float32))
    return Target("hpccg_1d", txt,
                  LintContext(target="hpccg_1d",
                              expected_permute_total=PERMUTES_HPCCG(1, 2)))


@target("hpccg_3d")
def _hpccg_3d() -> Target:
    """HPCCG on a 2x2x2 (planes x rows x cols) mesh, iters=2."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _hpccg_solver
    from repro.launch.mesh import make_grid_mesh

    f = _hpccg_solver(make_grid_mesh(2, 2, 2), ("planes", "rows", "cols"),
                      2, "hdot", 4)
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((12, 20, 20), jnp.float32))
    return Target("hpccg_3d", txt,
                  LintContext(target="hpccg_3d",
                              expected_permute_total=PERMUTES_HPCCG(3, 2)))


# ------------------------------------------------------------- grad sync
_SYNC_TREE_SIZES = {"embed": 11, "w1": 23, "w2": 37, "head": 53}
_SYNC_TREE_LAYERS = {"embed": 0, "w1": 1, "w2": 2, "head": 3}


def _grad_sync_jit(order: str, mode: str = "hdot"):
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.overlap import grad_sync
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",))
    specs = {k: jax.ShapeDtypeStruct((n,), jnp.float32)
             for k, n in _SYNC_TREE_SIZES.items()}
    f = jax.jit(jax.shard_map(
        functools.partial(grad_sync, axes="data", mode=mode, num_buckets=4,
                          layers=_SYNC_TREE_LAYERS, order=order),
        mesh=mesh, in_specs=(P(),), out_specs=P()))
    return f, specs


def _grad_sync_expected(order: str) -> List[int]:
    """Per-leaf all-reduce element counts in emission order, from
    make_buckets itself. A bucket is one multi-operand ``lax.psum``, but the
    pre-opt HLO carries one all-reduce instruction per leaf (consecutive
    channel ids), so the lint-level expectation is the flattened sequence."""
    import numpy as np

    from repro.core.overlap import make_buckets

    tree = {k: np.zeros((n,), np.float32)
            for k, n in _SYNC_TREE_SIZES.items()}
    buckets = make_buckets(tree, 4, layers=_SYNC_TREE_LAYERS, order=order)
    return [leaf.size for b in buckets for _, leaf in b]


@target("grad_sync_1d")
def _grad_sync_1d() -> Target:
    """Explicit HDOT grad sync: per-bucket psums, reverse-topo emission."""
    f, specs = _grad_sync_jit("reverse_topo")
    expected = _grad_sync_expected("reverse_topo")
    ctx = LintContext(target="grad_sync_1d", expected_permute_total=0,
                      expected_ar_elements=expected,
                      wire_dtype_elements={
                          "f32": sum(_SYNC_TREE_SIZES.values())})
    return Target("grad_sync_1d", _pre_opt_text(f, specs), ctx)


# ------------------------------------------------------------ lm steps
def _lm_trainer(parallel, mesh_shape, axes):
    from repro.config.base import RunConfig, TrainConfig
    from repro.config.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.runtime.trainer import Trainer

    cfg = get_arch("qwen3-8b").reduced()
    train = TrainConfig(global_batch=8, seq_len=32, warmup_steps=2,
                        total_steps=10, checkpoint_every=10**6,
                        checkpoint_dir="/tmp/repro_lint_ckpt")
    mesh = make_mesh(mesh_shape, axes)
    return Trainer(RunConfig(cfg, parallel, train), mesh=mesh), mesh


def _lm_specs(trainer):
    import jax

    from repro.optim import adamw_init

    pspec = trainer.model.abstract_params()
    ospec = jax.eval_shape(adamw_init, pspec)
    batch = trainer._augment_frontend(trainer.data.batch_at(0))
    bspec = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}
    return pspec, ospec, bspec


def _param_budget(pspec) -> Dict[str, int]:
    import jax
    import numpy as np

    budget: Dict[str, int] = {}
    for leaf in jax.tree.leaves(pspec):
        dt = hlo_dtype(leaf.dtype)
        budget[dt] = budget.get(dt, 0) + int(np.prod(leaf.shape))
    return budget


def _lm_hdot_target(name: str, mesh_shape, axes, overlap: str = "hdot"
                    ) -> Target:
    from repro.config.base import ParallelConfig

    par = ParallelConfig(param_shard=False, remat="none", overlap=overlap)
    trainer, _ = _lm_trainer(par, mesh_shape, axes)
    jitted = trainer._build_step()
    pspec, ospec, bspec = _lm_specs(trainer)
    ctx = LintContext(target=name, expected_permute_total=0,
                      wire_dtype_elements=_param_budget(pspec),
                      expect_donation=True)
    return Target(name, _pre_opt_text(jitted, pspec, ospec, bspec), ctx)


@target("lm_hdot_1d")
def _lm_hdot_1d() -> Target:
    """lm train step, explicit HDOT bucketed grad sync, 4-way DP."""
    return _lm_hdot_target("lm_hdot_1d", (4,), ("data",))


@target("lm_hdot_2d")
def _lm_hdot_2d() -> Target:
    """lm train step, HDOT grad sync over a 2-D (pod x data) DP mesh."""
    return _lm_hdot_target("lm_hdot_2d", (2, 2), ("pod", "data"))


@target("lm_fsdp_1d")
def _lm_fsdp_1d() -> Target:
    """lm FSDP (ZeRO-3) step: one RS + one AG per bucket, reverse emission."""
    import jax

    from repro.config.base import ParallelConfig
    from repro.launch.steps import fsdp_layout_for, make_fsdp_train_step
    from repro.optim import adamw_init

    par = ParallelConfig(param_shard=True, remat="none")
    trainer, mesh = _lm_trainer(par, (4,), ("data",))
    layout, _ = fsdp_layout_for(trainer.model, par, mesh)
    step_fn = make_fsdp_train_step(trainer.model, par, mesh,
                                   trainer.opt_cfg, layout=layout)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    n = layout.n_shards
    # global flat buffers (the step's shard_map splits them over the DP axes)
    pflat = {g.key: jax.ShapeDtypeStruct((g.padded,), g.dtype)
             for g in layout.groups}
    ospec = jax.eval_shape(adamw_init, pflat)
    _, _, bspec = _lm_specs(trainer)
    budget: Dict[str, int] = {}
    for g in layout.groups:
        dt = hlo_dtype(g.dtype)
        budget[dt] = budget.get(dt, 0) + g.padded // n
    ctx = LintContext(
        target="lm_fsdp_1d", expected_permute_total=0,
        expected_rs_elements=[g.padded // n for g in reversed(layout.groups)],
        expected_ag_elements=[g.padded for g in layout.groups],
        wire_dtype_elements=budget, expect_donation=True)
    return Target("lm_fsdp_1d", _pre_opt_text(jitted, pflat, ospec, bspec),
                  ctx)


def _lm_fsdp_streaming_pieces(streaming: bool):
    """Shared lowering for the streaming target and its gather-all mutation
    fixture: SAME per-layer layout, SAME model options; only the gather
    placement differs (inside each consuming layer's remat region vs a
    top-of-step gather-all)."""
    import jax

    from repro.config.base import ParallelConfig
    from repro.launch.steps import fsdp_layout_for, make_fsdp_train_step
    from repro.models.model import ModelOptions
    from repro.optim import adamw_init

    par = ParallelConfig(param_shard=True, fsdp_streaming=streaming,
                         scan_layers=False, remat="full",
                         bucket_order="layer")
    trainer, mesh = _lm_trainer(par, (4,), ("data",))
    trainer.options = ModelOptions(attn_impl="dense", scan_layers=False,
                                   remat="full", fused_xent=False)
    from repro.models.model import build_model

    trainer.model = build_model(trainer.run.model, trainer.options)
    layout, _ = fsdp_layout_for(trainer.model, par, mesh)
    step_fn = make_fsdp_train_step(trainer.model, par, mesh,
                                   trainer.opt_cfg, layout=layout)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    pflat = {g.key: jax.ShapeDtypeStruct((g.padded,), g.dtype)
             for g in layout.groups}
    ospec = jax.eval_shape(adamw_init, pflat)
    _, _, bspec = _lm_specs(trainer)
    n = layout.n_shards
    budget: Dict[str, int] = {}
    for g in layout.groups:
        dt = hlo_dtype(g.dtype)
        budget[dt] = budget.get(dt, 0) + g.padded // n
    return (jitted, pflat, ospec, bspec, layout, budget, par, trainer.model)


@target("lm_fsdp_streaming")
def _lm_fsdp_streaming() -> Target:
    """Streaming ZeRO-3 step: per-layer AG at point of use, regathered in the
    backward — pending-gather working set bounded by fsdp_working_set."""
    from repro.core.overlap import fsdp_stream

    jitted, pflat, ospec, bspec, layout, budget, par, model = (
        _lm_fsdp_streaming_pieces(True))
    n = layout.n_shards
    fwd = [g.padded for g in layout.groups]
    # the backward regathers every LAYER bucket in reverse layer order
    # (within a layer the remat retrace keeps forward order); the embed and
    # head buckets gather once — the take-backward never needs the table
    # primal and the head weight's residual spans only the forward/backward
    # boundary (models.model.train_loss_streamed)
    stream = fsdp_stream(layout, model.param_layers(), ("data",))
    layer_depths = [d for d in stream.depths
                    if d not in (0, max(stream.depths))]
    bwd = [g.padded for d in reversed(layer_depths)
           for g in stream.groups_at(d)]
    # RS emission = AD transpose order: head buckets first (their gathers
    # are the last forward consumers), then each layer's buckets as its
    # remat region replays (within-depth forward order), embed last
    rs = [g.padded // n
          for d in reversed(stream.depths) for g in stream.groups_at(d)]
    ctx = LintContext(
        target="lm_fsdp_streaming", expected_permute_total=0,
        expected_rs_elements=rs,
        expected_ag_elements=fwd + bwd,
        wire_dtype_elements=budget, expect_donation=True,
        extra={"fsdp_working_set": par.fsdp_working_set})
    return Target("lm_fsdp_streaming",
                  _pre_opt_text(jitted, pflat, ospec, bspec), ctx)


@broken("broken_gather_all_streaming")
def _broken_gather_all_streaming() -> Target:
    """Top-of-step gather-all on the SAME per-layer layout: every bucket's
    AG is pending at once, so only AG-ADJACENCY trips (the ctx expectations
    match this lowering's own emission: one AG per bucket forward-order, RS
    reversed)."""
    jitted, pflat, ospec, bspec, layout, budget, par, _ = (
        _lm_fsdp_streaming_pieces(False))
    n = layout.n_shards
    ctx = LintContext(
        target="broken_gather_all_streaming", expected_permute_total=0,
        expected_rs_elements=[g.padded // n for g in reversed(layout.groups)],
        expected_ag_elements=[g.padded for g in layout.groups],
        wire_dtype_elements=budget, expect_donation=True,
        extra={"fsdp_working_set": par.fsdp_working_set})
    return Target("broken_gather_all_streaming",
                  _pre_opt_text(jitted, pflat, ospec, bspec), ctx)


# ------------------------------------------------------------- moe EP a2a
def _lm_moe_grad_target(name: str, a2a_chunks: int) -> Target:
    """value_and_grad of the MoE EP layer (the same program
    ``tests/test_moe_ep.py`` checks numerically against the dense oracle) on
    a (1 data x 2 model) mesh: the model axis is non-trivial, so
    ``moe_apply`` takes the shard_map EP path and its all-to-alls are the
    only explicit collectives in the pre-opt HLO.

    Deliberately the LAYER grad, not the full lm train step: both the
    optimizer (``b1*m`` on every param leaf) and any vocab readout's
    label-side gradient seed (one-hot compare / take_along_axis scatter,
    B*S*V elements) are dataflow-independent of every trunk collective and
    would hand even the monolithic a2a a spurious NO-OVERLAP-WINDOW pass.
    The layer program keeps the window question honest: the only sized
    compute a forward dispatch/combine slice can be independent of is
    *another slice's* expert FFN, which is exactly the invariant the
    chunked schedule exists to create.

    ``scalar_elements`` is raised to 2048 so router bookkeeping (the aux
    one_hot is exactly B_loc*S_loc*K*E = 1024 elements here, the f_e/p_e
    pmeans 4) neither counts as an overlap window nor as sized traffic —
    only FFN-scale compute (>= 10240 elements/slice) can hide an a2a.
    """
    import jax
    import jax.numpy as jnp

    from repro.config.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models.moe import moe_apply, moe_specs
    from repro.sharding.rules import use_sharding

    cfg = get_arch("qwen3-moe-30b-a3b").reduced()
    mesh = make_mesh((1, 2), ("data", "model"))

    def loss(p, x):
        y, aux = moe_apply(p, x, cfg, a2a_chunks=a2a_chunks)
        return jnp.sum(y.astype(jnp.float32) ** 2) + aux

    # grads w.r.t. params AND activations: in the full lm, d_x flows to the
    # previous layer through the transposed dispatch a2a — dropping it would
    # silently halve the backward a2a count
    jitted = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    pspec = {k: jax.ShapeDtypeStruct(s.shape, s.dtype)
             for k, s in moe_specs(cfg).items()}
    xspec = jax.ShapeDtypeStruct((8, 32, cfg.d_model), jnp.bfloat16)
    # the EP path is selected at trace time from current_context()
    with use_sharding(mesh):
        txt = _pre_opt_text(jitted, pspec, xspec)
    ctx = LintContext(target=name, expected_permute_total=0,
                      expected_a2a_total=A2AS_MOE(a2a_chunks),
                      scalar_elements=2048)
    return Target(name, txt, ctx)


@target("lm_moe_ep")
def _lm_moe_ep() -> Target:
    """MoE EP grads, a2a_scan chunked (Q=2): every a2a slice overlaps FFN."""
    return _lm_moe_grad_target("lm_moe_ep", 2)


def _decode_tp_target(name: str, mode: str) -> Target:
    """One TP-sharded continuous-batching decode step (models.decode_tp —
    the `BatchServer(decode_step_fn=...)` cell) on a (1 data x 2 model)
    mesh: 4L+1 collective-matmul rings (fused QKV ag, wo rs, fused gate|up
    ag, down rs per layer, plus the unembed ag), per-slot ring caches
    donated.

    `scalar_elements` is raised to 128 so the per-slot bookkeeping — cache
    `pos` compares / causal masks (slots*w = 128 elements here) and the rope
    angle tables ((slots, 1, hd/2) = 128) — neither counts as an overlap
    window nor as sized traffic; only ring-piece-scale matmul output
    (>= 256 elements) can hide a ppermute, which is exactly the chunk
    compute the hdot schedule creates. Cache writes are per-row
    dynamic-update-slices (NOT scatters) for the same reason — assembling a
    block is not compute (analysis/hlo_ir.COMPUTE_OPS), so the two-phase
    fixture cannot borrow an overlap window from its own cache updates.
    """
    import functools

    import jax
    import jax.numpy as jnp

    from repro.config.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.models.decode_tp import build_decode_step, expected_permute_total
    from repro.models.model import ModelOptions, build_model
    from repro.runtime.server import make_slot_caches

    cfg = get_arch("qwen3-8b").reduced()     # dense GQA + qk-norm
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    mesh = make_mesh((1, 2), ("data", "model"))
    slots, max_len = 8, 16
    jitted = jax.jit(build_decode_step(model, mesh, mode=mode),
                     donate_argnums=(2,))
    pspec = model.abstract_params()
    cspec = jax.eval_shape(
        functools.partial(make_slot_caches, model, slots, max_len))
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32)
    txt = _pre_opt_text(jitted, pspec, tok, cspec, pos)
    expected = (expected_permute_total(cfg, slots, 1, 2)
                if mode == "hdot" else 0)
    ctx = LintContext(target=name, expected_permute_total=expected,
                      max_exposed_collectives=0, expect_donation=True,
                      scalar_elements=128)
    return Target(name, txt, ctx)


@target("lm_decode_tp")
def _lm_decode_tp() -> Target:
    """TP continuous-decode step: (4L+1) hdot rings, zero exposed permutes."""
    return _decode_tp_target("lm_decode_tp", "hdot")


# ------------------------------------------------- mutation fixtures
@broken("broken_unpeeled_halo1d")
def _broken_unpeeled() -> Target:
    """PR-3 regression: unpeeled drain — dead exchange + wrong pair count."""
    steps = 2
    jitted, spec = _halo_jit(1, steps, peel=False)
    ctx = LintContext(target="broken_unpeeled_halo1d",
                      expected_permute_total=PERMUTES_HALO(1, steps),
                      expect_donation=True)
    return Target("broken_unpeeled_halo1d", _pre_opt_text(jitted, spec), ctx)


@broken("broken_no_donate_halo1d")
def _broken_no_donate() -> Target:
    """Donation dropped from the canonical halo jit."""
    jitted, spec = _halo_jit(1, 2, peel=True, donate=False)
    ctx = LintContext(target="broken_no_donate_halo1d",
                      expected_permute_total=PERMUTES_HALO(1, 2),
                      expect_donation=True)
    return Target("broken_no_donate_halo1d", _pre_opt_text(jitted, spec), ctx)


@broken("broken_tree_grad_sync")
def _broken_tree_order() -> Target:
    """Buckets emitted shallowest-first (order='tree') — wrong emission."""
    f, specs = _grad_sync_jit("tree")
    ctx = LintContext(target="broken_tree_grad_sync",
                      expected_ar_elements=_grad_sync_expected("reverse_topo"))
    return Target("broken_tree_grad_sync", _pre_opt_text(f, specs), ctx)


@broken("broken_two_phase_grad_sync")
def _broken_two_phase_sync() -> Target:
    """Monolithic two-phase psum of a mixed-dtype tree: the concat upcasts
    bf16 grads to f32 — full-width wire traffic (WIRE-WIDEN)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.overlap import grad_sync
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",))
    specs = {"wq": jax.ShapeDtypeStruct((64, 8), jnp.bfloat16),
             "norm": jax.ShapeDtypeStruct((64,), jnp.float32)}
    f = jax.jit(jax.shard_map(
        functools.partial(grad_sync, axes="data", mode="two_phase"),
        mesh=mesh, in_specs=(P(),), out_specs=P()))
    ctx = LintContext(target="broken_two_phase_grad_sync",
                      wire_dtype_elements={"bf16": 64 * 8, "f32": 64})
    return Target("broken_two_phase_grad_sync", _pre_opt_text(f, specs), ctx)


@broken("broken_two_phase_heat2d")
def _broken_two_phase_heat2d() -> Target:
    """two_phase heat2d: exchange -> barrier -> compute, nothing overlaps."""
    import jax
    import jax.numpy as jnp

    from repro.core.stencil import _heat2d_solver
    from repro.launch.mesh import make_mesh

    f = _heat2d_solver(make_mesh((4,), ("data",)), ("data",), 2, "two_phase",
                       4)
    txt = _pre_opt_text(f, jax.ShapeDtypeStruct((32, 32), jnp.float32))
    return Target("broken_two_phase_heat2d", txt,
                  LintContext(target="broken_two_phase_heat2d"))


@broken("broken_two_phase_decode_tp")
def _broken_two_phase_decode_tp() -> Target:
    """Two-phase TP decode: serial all_gather / psum_scatter walls around
    every projection matmul — GSPMD's schedule. Every sized op is an
    ancestor or descendant of the collective next to it (the per-row cache
    DUS writes don't count as compute), so NO-OVERLAP-WINDOW fires on each
    wall; the pair count (0 permutes) stays green so the failure is
    attributed to the schedule shape, not a miscount."""
    return _decode_tp_target("broken_two_phase_decode_tp", "two_phase")


@broken("broken_monolithic_a2a_moe")
def _broken_monolithic_a2a() -> Target:
    """Monolithic MoE a2a (Q=1): dispatch/combine with zero overlap window.

    The lint context still expects the monolithic pair count (4 a2as: the
    un-chunked fwd+bwd dispatch/combine), so PAIR-COUNT stays green and the
    failure is attributed to the schedule shape: NO-OVERLAP-WINDOW fires
    because every sized op in the module is an ancestor or descendant of
    the bulk a2as — nothing can hide them."""
    return _lm_moe_grad_target("broken_monolithic_a2a_moe", 1)


@broken("broken_double_gather_fsdp")
def _broken_double_gather() -> Target:
    """fsdp_all_gather called twice per step: two AGs per bucket buffer."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.overlap import fsdp_all_gather, fsdp_layout
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("data",))
    tree = {"wq": jax.ShapeDtypeStruct((64, 8), jnp.float32),
            "wk": jax.ShapeDtypeStruct((32, 8), jnp.float32)}
    layout = fsdp_layout(tree, 4, num_buckets=2)

    def local(flat):
        a = fsdp_all_gather(flat, layout, ("data",))
        b = fsdp_all_gather(flat, layout, ("data",))
        return sum(jnp.sum(x) + jnp.sum(y)
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    specs = {g.key: jax.ShapeDtypeStruct((g.padded,), g.dtype)
             for g in layout.groups}
    f = jax.jit(jax.shard_map(local, mesh=mesh,
                              in_specs=(P("data"),), out_specs=P(),
                              check_vma=False))
    ctx = LintContext(
        target="broken_double_gather_fsdp",
        expected_ag_elements=[g.padded for g in layout.groups])
    return Target("broken_double_gather_fsdp", _pre_opt_text(f, specs), ctx)
