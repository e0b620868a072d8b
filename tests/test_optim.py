"""Optimizer + compression: AdamW against a NumPy reference, moment dtypes,
chunked update equivalence, int8 error-feedback properties."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         bf16_compress, bf16_decompress, ef_compress_update,
                         fp8_compress, fp8_decompress, int8_compress,
                         int8_decompress, warmup_cosine, wire_codec)


def _numpy_adamw(g, m, v, p, lr, cfg, step):
    g = 1.0 * g  # no clip when gnorm small (clip factor == 1 in this regime)
    b1, b2 = cfg.beta1, cfg.beta2
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** step)
    vh = v / (1 - b2 ** step)
    return p - lr * (mh / (np.sqrt(vh) + cfg.eps) + cfg.weight_decay * p), m, v


def test_adamw_matches_numpy_reference():
    cfg = AdamWConfig(lr=1e-2, grad_clip=1e9)  # disable clip for the oracle
    params = {"w": jnp.asarray([[0.5, -0.25], [1.0, 2.0]], jnp.float32)}
    state = adamw_init(params)
    g = {"w": jnp.asarray([[0.1, -0.2], [0.3, 0.05]], jnp.float32)}
    p_np = np.asarray(params["w"]).copy()
    m_np = np.zeros_like(p_np)
    v_np = np.zeros_like(p_np)
    for step in range(1, 4):
        params, state, _ = adamw_update(g, state, params, cfg,
                                        jnp.asarray(1e-2))
        p_np, m_np, v_np = _numpy_adamw(np.asarray(g["w"]), m_np, v_np, p_np,
                                        1e-2, cfg, step)
        np.testing.assert_allclose(np.asarray(params["w"]), p_np,
                                   rtol=1e-5, atol=1e-6)


def test_adamw_grad_clip_caps_update():
    cfg = AdamWConfig(lr=1.0, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros((4,), jnp.float32)}
    state = adamw_init(params)
    g = {"w": jnp.full((4,), 100.0)}
    _, _, gnorm = adamw_update(g, state, params, cfg, jnp.asarray(1.0))
    assert float(gnorm) == pytest.approx(200.0)  # reported pre-clip


def test_adamw_moment_dtype_preserved():
    params = {"w": jnp.zeros((8,), jnp.bfloat16)}
    state = adamw_init(params, moment_dtype=jnp.bfloat16)
    g = {"w": jnp.ones((8,), jnp.bfloat16)}
    params, state, _ = adamw_update(g, state, params, AdamWConfig(),
                                    jnp.asarray(1e-3))
    assert state["m"]["w"].dtype == jnp.bfloat16
    assert state["v"]["w"].dtype == jnp.bfloat16
    assert params["w"].dtype == jnp.bfloat16


def test_adamw_chunked_equals_plain():
    """chunk_leading (per-layer lax.map update) must be a pure perf knob."""
    L = 6
    params = {"stack": jnp.arange(L * 8, dtype=jnp.float32).reshape(L, 8) / 10,
              "flat": jnp.ones((5,), jnp.float32)}
    g = jax.tree.map(lambda p: 0.1 * jnp.ones_like(p), params)
    cfg = AdamWConfig()
    s1 = adamw_init(params)
    s2 = adamw_init(params)
    p1, s1, _ = adamw_update(g, s1, params, cfg, jnp.asarray(1e-3),
                             chunk_leading=0)
    p2, s2, _ = adamw_update(g, s2, params, cfg, jnp.asarray(1e-3),
                             chunk_leading=L)
    for k in params:
        np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                   rtol=1e-6, atol=1e-7)


def test_warmup_cosine_shape():
    lr0 = float(warmup_cosine(0, 1e-3, 100, 1000))
    lr_w = float(warmup_cosine(100, 1e-3, 100, 1000))
    lr_end = float(warmup_cosine(1000, 1e-3, 100, 1000))
    assert lr0 == 0.0
    assert lr_w == pytest.approx(1e-3, rel=1e-3)
    assert lr_end == pytest.approx(1e-4, rel=1e-2)  # final_frac=0.1


# ------------------------------------------------------------- compression
@given(scale=st.floats(0.01, 100.0))
@settings(max_examples=30, deadline=None)
def test_int8_roundtrip_bounded_error(scale):
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * scale
    payload = int8_compress(x)
    y = int8_decompress(payload)
    max_err = float(jnp.max(jnp.abs(x - y)))
    # quantization step = max|x| / 127
    assert max_err <= float(jnp.max(jnp.abs(x))) / 127 + 1e-6


@pytest.mark.parametrize("scale", [1e-3, 0.1, 1.0, 37.0, 1e3])
def test_bf16_roundtrip_relative_error(scale):
    """bf16 shares f32's exponent, so round-trip error is purely the 8-bit
    significand: elementwise relative error <= 2^-8 at any magnitude."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * scale
    y = bf16_decompress(bf16_compress(x))
    assert y.dtype == jnp.float32
    err = np.abs(np.asarray(x) - np.asarray(y))
    bound = np.abs(np.asarray(x)) * 2.0**-8 + 1e-38
    np.testing.assert_array_less(err, bound)


@pytest.mark.parametrize("scale", [1e-3, 0.1, 1.0, 37.0, 1e3])
def test_fp8_roundtrip_bounded_error(scale):
    """fp8 e4m3 with a per-tensor scale: normal values round to 3 mantissa
    bits (rel err <= 2^-3), the subnormal tail to an absolute step of the
    scaled quantum — both bounds independent of the tensor's magnitude."""
    x = jax.random.normal(jax.random.PRNGKey(0), (256,)) * scale
    payload = fp8_compress(x)
    y = fp8_decompress(payload)
    err = np.abs(np.asarray(x) - np.asarray(y))
    s = float(payload["scale"])
    bound = np.maximum(np.abs(np.asarray(x)) * 2.0**-3, s * 2.0**-9) + 1e-38
    assert (err <= bound).all()


def test_fp8_scale_saturates_at_amax():
    # the largest-magnitude element maps exactly onto the e4m3 max (448):
    # nothing clips, and decompress restores it to full precision
    x = jnp.array([-7.0, 0.5, 3.5])
    y = fp8_decompress(fp8_compress(x))
    np.testing.assert_allclose(float(y[0]), -7.0, rtol=1e-6)


def test_wire_codec_registry():
    for kind in ("bf16", "fp8", "int8"):
        compress, decompress = wire_codec(kind)
        x = jax.random.normal(jax.random.PRNGKey(2), (32,))
        y = decompress(compress(x))
        assert y.shape == x.shape and y.dtype == jnp.float32
    with pytest.raises(ValueError, match="unknown wire codec"):
        wire_codec("fp4")


def test_error_feedback_with_fp8_codec_converges():
    """EF composes with any wire codec: the fp8 residual is carried, so the
    mean of sent updates converges to the true gradient."""
    g = jax.random.normal(jax.random.PRNGKey(3), (64,)) * 0.1
    err = jnp.zeros_like(g)
    sent = []
    for _ in range(50):
        payload, err = ef_compress_update(
            g, err, compress=fp8_compress, decompress=fp8_decompress)
        sent.append(fp8_decompress(payload))
    avg = np.mean(np.stack([np.asarray(s) for s in sent]), axis=0)
    np.testing.assert_allclose(avg, np.asarray(g), rtol=0.08, atol=0.02)


def test_error_feedback_accumulates_residual():
    """EF: the compression residual is carried, so the MEAN of quantized
    updates converges to the true gradient (unbiased in the long run)."""
    g = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 0.1
    err = jnp.zeros_like(g)
    sent = []
    for _ in range(50):
        payload, err = ef_compress_update(g, err)
        sent.append(int8_decompress(payload))
    avg = np.mean(np.stack([np.asarray(s) for s in sent]), axis=0)
    np.testing.assert_allclose(avg, np.asarray(g), rtol=0.08, atol=0.02)
