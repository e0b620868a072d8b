"""The main path's Pallas kernels, one decode step and the Heat2D solve
compiled for a described (not attached) TPU v5e chip at real widths: what
the TPU compiler refuses (block shapes off the (8, 128) tiling, too much
VMEM, a program that does not fit HBM) or the HBM passes it adds (a block
copied or concatenated inside the sweep loop) fail here without a chip.
Nothing runs.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file. Keep all such compiles in this one file."""
from __future__ import annotations

import dataclasses
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config.registry import get_arch
from repro.models.model import ModelOptions, build_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    entries compiled for a described chip cannot be read back without it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


def test_heat2d_kernel_compiles(one_chip):
    from repro.kernels.heat2d.ops import heat2d_sweep

    c = _compile(lambda u: heat2d_sweep(u, tile=(256, 256), sweeps=2,
                                        impl="pallas", interpret=False),
                 _spec(one_chip, (4096, 4096)))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_kernel_compiles_at_mamba2_widths(one_chip):
    from repro.kernels.ssd_scan.ops import ssd

    cfg = get_arch("mamba2-780m")
    s = cfg.ssm
    b, l, h, p, n = 1, 1024, s.num_heads(cfg.d_model), s.head_dim, s.state_dim
    assert (h, p, n, s.chunk_size) == (48, 64, 128, 256)
    c = _compile(lambda x, dt, A, B, C: ssd(x, dt, A, B, C, s.chunk_size,
                                            impl="pallas", interpret=False),
                 _spec(one_chip, (b, l, h, p), jnp.bfloat16),
                 _spec(one_chip, (b, l, h)), _spec(one_chip, (h,)),
                 _spec(one_chip, (b, l, n), jnp.bfloat16),
                 _spec(one_chip, (b, l, n), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_lru_kernel_compiles_at_batch_2(one_chip):
    lru = importlib.import_module("repro.kernels.lru_scan.ops")
    w = get_arch("recurrentgemma-2b").hybrid.lru_width
    assert w == 2560
    c = _compile(lambda a, x, h0: lru.lru_scan(a, x, h0, impl="pallas",
                                               interpret=False),
                 _spec(one_chip, (2, 1024, w)), _spec(one_chip, (2, 1024, w)),
                 _spec(one_chip, (2, w)))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles_at_internlm2_widths(one_chip):
    fa = importlib.import_module("repro.kernels.flash_attention.ops")
    cfg = get_arch("internlm2-1.8b")
    hd = cfg.resolved_head_dim
    c = _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                    impl="pallas",
                                                    interpret=False),
                 _spec(one_chip, (1, 4096, cfg.num_heads, hd), jnp.bfloat16),
                 _spec(one_chip, (1, 4096, cfg.num_kv_heads, hd), jnp.bfloat16),
                 _spec(one_chip, (1, 4096, cfg.num_kv_heads, hd), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_internlm2_decode_step_compiles_full_width(one_chip):
    """One continuous-batching decode step (8 slots, per-slot positions) of
    internlm2-1.8b at its published widths, cut to 2 layers."""
    from repro.runtime.server import make_slot_caches

    cfg = dataclasses.replace(get_arch("internlm2-1.8b"), num_layers=2)
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    slots, max_len = 8, 1024

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = on_chip(model.abstract_params())
    caches = on_chip(jax.eval_shape(lambda: make_slot_caches(model, slots, max_len)))
    lowered = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, _spec(one_chip, (slots, 1), jnp.int32), caches,
        _spec(one_chip, (slots,), jnp.int32))
    compiled = lowered.compile()
    logits, _ = lowered.out_info
    assert logits.shape == (slots, 1, cfg.vocab_size)
    mem = compiled.memory_analysis()
    # the caches are donated: updated in place, not copied out
    assert mem.alias_size_in_bytes > 0


_FUSED = re.compile(r"calls=%?([\w.\-]+)")


def _loop_ops(module, opcodes, shape):
    """For each while loop of `module`, the ops of `opcodes` with result
    `shape` in its computations and in everything they call."""
    def callees(i):
        return [*i.called_computations, *_FUSED.findall(i.attr_text)]

    loops = {}
    for _, w in module.all_instructions():
        if w.opcode != "while":
            continue
        todo, seen, found = callees(w), set(), []
        while todo:
            name = todo.pop()
            if name in seen or name not in module.computations:
                continue
            seen.add(name)
            for i in module.computations[name].instructions:
                todo.extend(callees(i))
                if i.opcode in opcodes and any(d == shape for _, d in i.shapes):
                    found.append((i.opcode, i.name))
        loops[w.name] = found
    assert loops, "the compiled solve has no while loop"
    return loops


def _loop_instructions(module):
    """For each while loop of `module`, every instruction of its computations
    and of everything they call, each with the name of its computation."""
    out = {}
    for _, w in module.all_instructions():
        if w.opcode != "while":
            continue
        todo, seen, found = list(w.called_computations), set(), []
        while todo:
            name = todo.pop()
            if name in seen or name not in module.computations:
                continue
            seen.add(name)
            for i in module.computations[name].instructions:
                todo.extend([*i.called_computations,
                             *_FUSED.findall(i.attr_text)])
                found.append((i, name))
        out[w.name] = found
    assert out, "the compiled solve has no while loop"
    return out


# (mesh, block-sized copies a loop may hold). The concatenating schedule
# copied two blocks a sweep; the unaligned task writes left one relayout a
# trip on 2x2; the tile-aligned writes leave none.
@pytest.mark.parametrize("mesh_shape,copies", [((1, 1), 0), ((2, 2), 0)],
                         ids=["1x1", "2x2"])
def test_heat2d_hdot_loop_writes_blocks_in_place(topo, one_chip, mesh_shape,
                                                 copies):
    """The hdot sweep loop writes each task's cells into the carried spare
    block: no block-sized concatenate inside it, and no block-sized copy.
    The task boxes lie on (8, 128) tiles, so each task's stencil fuses into
    its write: every block-shaped `dynamic-update-slice` is an output of a
    fusion that also holds the stencil's adds, one for each of the 20 tasks
    (16 chunks, 4 faces) of each of the trip's 2 sweeps; none stands alone,
    and no fusion writes a chunk to a temporary of its own."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.analysis.hlo_ir import parse_hlo_module
    from repro.core.stencil import _heat2d_solver
    from repro.launch.mesh import GRID_AXES

    n = 2048  # cells a chip along each dim
    devs = np.array(topo.devices[:int(np.prod(mesh_shape))]).reshape(mesh_shape)
    mesh = Mesh(devs, GRID_AXES)
    solve = _heat2d_solver(mesh, GRID_AXES, 10, "hdot", 4, None)
    glob = tuple(n * m for m in mesh_shape)
    c = solve.lower(_spec(NamedSharding(mesh, P(*GRID_AXES)), glob)).compile()
    module = parse_hlo_module(c.as_text())
    loops = _loop_ops(module, ("copy", "copy-start", "concatenate"), (n, n))
    for ops in loops.values():
        assert "concatenate" not in {op for op, _ in ops}, loops
        assert len(ops) <= copies, loops
    for found in _loop_instructions(module).values():
        fusions = {_FUSED.findall(i.attr_text)[0]: i
                   for i, _ in found if i.opcode == "fusion"}
        writes = []
        for i, where in found:
            if i.opcode == "fusion":  # no chunk-sized temporary
                assert not any(len(d) == 2 and d != (n, n) and min(d) >= n // 8
                               for _, d in i.shapes), (i.name, i.shapes)
            if i.opcode != "dynamic-update-slice" or i.shapes[0][1] != (n, n):
                continue
            assert where in fusions, f"standalone {i.name}"
            comp = module.computations[where]
            root = comp.root
            outs = root.operands if root.opcode == "tuple" else (root.name,)
            assert i.name in {o.lstrip("%") for o in outs}, (where, i.name)
            adds = sum(x.opcode == "add" for x in comp.instructions)
            assert adds >= 3, (where, adds)  # the 5-point stencil's sum
            writes.append(fusions[where].name)
        assert len(writes) == 2 * 20, writes
