"""Per-kernel validation: Pallas (interpret=True on CPU) vs the pure-jnp
ref.py oracle, swept over shapes/dtypes; plus algorithm-level properties
(chunked SSD == sequential recurrence, red-black GS convergence)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.heat2d import ops as heat_ops
from repro.kernels.lru_scan import ops as lru_ops
from repro.kernels.lru_scan import ref as lru_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref


def _key(i=0):
    return jax.random.PRNGKey(i)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,hq,hkv,d,causal,window", [
    (1, 256, 256, 4, 4, 64, True, None),      # MHA causal
    (2, 256, 256, 8, 2, 64, True, None),      # GQA 4:1
    (1, 512, 512, 4, 1, 128, True, 128),      # MQA + sliding window
    (1, 128, 128, 2, 2, 32, False, None),     # bidirectional
])
def test_flash_vs_ref(b, sq, sk, hq, hkv, d, causal, window, dtype):
    k0 = _key(0)
    q = jax.random.normal(k0, (b, sq, hq, d), dtype)
    k = jax.random.normal(_key(1), (b, sk, hkv, d), dtype)
    v = jax.random.normal(_key(2), (b, sk, hkv, d), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 impl="pallas", interpret=True,
                                 block_q=128, block_k=128)
    want = fa_ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_block_shape_invariance():
    """Result must not depend on the BlockSpec tile choice."""
    q = jax.random.normal(_key(0), (1, 512, 4, 64), jnp.float32)
    k = jax.random.normal(_key(1), (1, 512, 2, 64), jnp.float32)
    v = jax.random.normal(_key(2), (1, 512, 2, 64), jnp.float32)
    outs = [fa_ops.flash_attention(q, k, v, impl="pallas", interpret=True,
                                   block_q=bq, block_k=bk)
            for bq, bk in [(128, 128), (256, 128), (128, 256), (512, 512)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------- heat2d
@pytest.mark.parametrize("n,tile", [(128, (64, 64)), (256, (128, 128)),
                                    (256, (256, 256))])
def test_heat2d_pallas_vs_ref(n, tile):
    u = jax.random.normal(_key(3), (n, n), jnp.float32)
    got = heat_ops.heat2d_sweep(u, tile=tile, impl="pallas", interpret=True)
    want = heat_ops.heat2d_sweep(u, tile=tile, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_heat2d_sweeps_converge():
    """Red-black GS on the Laplace problem must contract toward 0 with
    Dirichlet-0 boundaries."""
    u = jnp.ones((128, 128), jnp.float32)
    norms = [float(jnp.abs(u).mean())]
    for _ in range(5):
        u = heat_ops.heat2d_sweep(u, tile=(128, 128), sweeps=4, impl="ref")
        norms.append(float(jnp.abs(u).mean()))
    assert norms[-1] < norms[0]
    assert all(b <= a + 1e-6 for a, b in zip(norms, norms[1:]))


# ----------------------------------------------------------------- ssd scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 128, 2, 16, 8, 32),
    (2, 256, 4, 32, 16, 64),
    (1, 64, 1, 8, 4, 64),      # single chunk
])
def test_ssd_pallas_vs_ref(b, l, h, p, n, chunk, dtype):
    x = jax.random.normal(_key(0), (b, l, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(_key(1), (b, l, h)))
    A = -jnp.exp(jax.random.normal(_key(2), (h,)) * 0.2)
    B = jax.random.normal(_key(3), (b, l, n), dtype)
    C = jax.random.normal(_key(4), (b, l, n), dtype)
    yp, sp = ssd_ops.ssd(x, dt, A, B, C, chunk, impl="pallas", interpret=True)
    yr, sr = ssd_ref.ssd_ref(x, dt, A, B, C, chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(yp, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                               rtol=tol, atol=tol)


@given(chunk=st.sampled_from([8, 16, 32, 64]))
@settings(max_examples=8, deadline=None)
def test_ssd_chunked_equals_sequential(chunk):
    """The chunked SSD algorithm (any chunk size) must equal the O(l)
    sequential recurrence — the state hand-off is the sequence 'halo'."""
    b, l, h, p, n = 1, 64, 2, 8, 4
    x = jax.random.normal(_key(0), (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(_key(1), (b, l, h)))
    A = -jnp.exp(jax.random.normal(_key(2), (h,)) * 0.2)
    B = jax.random.normal(_key(3), (b, l, n), jnp.float32)
    C = jax.random.normal(_key(4), (b, l, n), jnp.float32)
    yc, sc = ssd_ref.ssd_ref(x, dt, A, B, C, chunk)
    ys, ss = ssd_ref.ssd_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(ys),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(ss),
                               rtol=1e-3, atol=1e-3)


def test_ssd_decode_matches_prefill():
    """Decoding one token against the prefill-final state must equal running
    the full sequence one step longer."""
    b, l, h, p, n = 1, 32, 2, 8, 4
    x = jax.random.normal(_key(0), (b, l + 1, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(_key(1), (b, l + 1, h)))
    A = -jnp.exp(jax.random.normal(_key(2), (h,)) * 0.2)
    B = jax.random.normal(_key(3), (b, l + 1, n), jnp.float32)
    C = jax.random.normal(_key(4), (b, l + 1, n), jnp.float32)
    _, state = ssd_ref.ssd_ref(x[:, :l], dt[:, :l], A, B[:, :l], C[:, :l], 16)
    y1, s1 = ssd_ref.ssd_decode_step_ref(state, x[:, l], dt[:, l], A,
                                         B[:, l], C[:, l])
    y_full, s_full = ssd_ref.ssd_sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_full[:, -1]),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s_full),
                               rtol=1e-3, atol=1e-3)


# ----------------------------------------------------------------- lru scan
@pytest.mark.parametrize("b,l,w", [(1, 64, 16), (2, 128, 32), (1, 33, 8)])
def test_lru_pallas_vs_ref(b, l, w):
    a = jax.random.uniform(_key(0), (b, l, w), minval=0.5, maxval=0.99)
    x = jax.random.normal(_key(1), (b, l, w))
    hp, lp = lru_ops.lru_scan(a, x, impl="pallas", interpret=True)
    hr, lr = lru_ref.lru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lr),
                               rtol=1e-5, atol=1e-5)


def test_lru_ref_vs_sequential():
    a = jax.random.uniform(_key(0), (2, 64, 8), minval=0.1, maxval=0.95)
    x = jax.random.normal(_key(1), (2, 64, 8))
    h0 = jax.random.normal(_key(2), (2, 8))
    hr, lr = lru_ref.lru_scan_ref(a, x, h0)
    hs, ls = lru_ref.lru_scan_sequential(a, x, h0)
    np.testing.assert_allclose(np.asarray(hr), np.asarray(hs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ls),
                               rtol=1e-5, atol=1e-5)


def test_heat2d_pallas_strip_halos_multi_tile_multi_sweep():
    """Strip-halo staging must reproduce the full-tile oracle when halos cross
    many tile boundaries and sweeps>1 reuse the VMEM-resident tile."""
    u = jax.random.normal(_key(7), (128, 128), jnp.float32)
    for tile, sweeps in [((32, 64), 3), ((64, 64), 2), ((128, 128), 4)]:
        got = heat_ops.heat2d_sweep(u, tile=tile, sweeps=sweeps,
                                    impl="pallas", interpret=True)
        want = heat_ops.heat2d_sweep(u, tile=tile, sweeps=sweeps, impl="ref")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
