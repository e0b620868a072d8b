"""Pallas TPU kernel: Mamba-2 SSD within-chunk terms.

One grid step = one (batch, chunk, head) task-level subdomain. The kernel
computes the chunk-local quantities (decay matrix L from the chunk's
cumulative log-decay, the masked C B^T "attention" matmul on the MXU, the
chunk input-state contribution); the cumulative sums, the tiny cross-chunk
recurrence (c steps over a (p, n) state) and the off-diagonal C @ state
matmul run in jnp outside — the state hand-off is the sequence halo between
subdomains.

Layout: the TPU lowering only accepts blocks whose last two dims are
multiples of (8, 128) or span the array, so per-head operands are laid out
head-major — x and y as (b, c, h, q, p), the per-step scalars dt and the
cumulative log-decay as (q, 1) columns (and the latter once more as a (1, q)
row) — and every block's last two dims are (q, p), (q, n), (n, p), (q, 1) or
(1, q).

VMEM per step ~ q*p + 2*q*n + 2*q*q floats; defaults (q=256, p=64, n=128)
~ 0.9 MB. q x q and q x n tiles are MXU-aligned (multiples of 128 for n,
q chosen as a multiple of 128 in production configs).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, dt_ref, csc_ref, csr_ref, b_ref, c_ref, ydiag_ref,
            states_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)                # (q, p)
    dt = dt_ref[0, 0, 0]                                   # (q, 1)
    cs_col = csc_ref[0, 0, 0]                              # (q, 1)
    cs_row = csr_ref[0, 0, 0]                              # (1, q)
    B = b_ref[0, 0].astype(jnp.float32)                   # (q, n)
    C = c_ref[0, 0].astype(jnp.float32)                   # (q, n)
    q = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(jj <= ii, jnp.exp(cs_col - cs_row), 0.0)  # (q, q)

    att = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))   # (q, q)
    xdt = x * dt                                           # (q, p)
    ydiag_ref[0, 0, 0] = (att * L @ xdt).astype(ydiag_ref.dtype)

    decay_states = jnp.exp(cs_col[q - 1:q, :] - cs_col)    # (q, 1)
    st = jax.lax.dot_general(B * decay_states, xdt,
                             (((0,), (0,)), ((), ())))     # (n, p)
    states_ref[0, 0, 0] = st.astype(states_ref.dtype)


def ssd_pallas(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, chunk: int, initial_state=None,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Same contract as kernels.ssd_scan.ref.ssd_ref."""
    b, l, h, p = x.shape
    n = B.shape[-1]
    assert l % chunk == 0
    c, q = l // chunk, chunk
    xc = jnp.swapaxes(x.reshape(b, c, q, h, p), 2, 3)     # (b, c, h, q, p)
    dtc = jnp.swapaxes(dt.astype(jnp.float32).reshape(b, c, q, h), 2, 3)
    cs = jnp.cumsum(dtc * A.astype(jnp.float32)[:, None], axis=-1)  # (b,c,h,q)
    Bc = B.reshape(b, c, q, n)
    Cc = C.reshape(b, c, q, n)

    def col(t):
        return t[..., None]                                # (b, c, h, q, 1)

    def head_block(*tail):
        return pl.BlockSpec((1, 1, 1) + tail,
                            lambda ib, ic, ih: (ib, ic, ih) + (0,) * len(tail))

    y_diag, states = pl.pallas_call(
        _kernel,
        grid=(b, c, h),
        in_specs=[
            head_block(q, p),
            head_block(q, 1),
            head_block(q, 1),
            head_block(1, q),
            pl.BlockSpec((1, 1, q, n), lambda ib, ic, ih: (ib, ic, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda ib, ic, ih: (ib, ic, 0, 0)),
        ],
        out_specs=[head_block(q, p), head_block(n, p)],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, c, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xc, col(dtc), col(cs), cs[..., None, :], Bc, Cc)

    decay_in = jnp.exp(cs)                                 # (b, c, h, q)
    decay_chunk = decay_in[..., -1]                        # (b, c, h)
    s0 = (jnp.zeros((b, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    def step(carry, inp):
        st_c, dec_c = inp                                  # (b,h,n,p), (b,h)
        prev = carry
        new = prev * dec_c[..., None, None] + jnp.swapaxes(st_c, -1, -2)
        return new, prev

    final, prev_states = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(decay_chunk, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # (b,c,h,p,n)

    y_off = jnp.einsum("bcqn,bchpn,bchq->bchqp", Cc.astype(jnp.float32),
                       prev_states, decay_in)
    y = jnp.swapaxes(y_diag + y_off, 2, 3).reshape(b, l, h, p).astype(x.dtype)
    return y, final
