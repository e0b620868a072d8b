"""Logical-axis resolver: greedy candidates, divisibility fixups, no mesh-axis
reuse within a tensor — the mechanism that lets one rule set drive all 10
architectures (sharding/rules docstring)."""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (ShardingContext, resolve_pspec,
                                  use_sharding, with_logical)


@pytest.fixture(scope="module")
def ctx256():
    """Resolver-only context with a fake 16x16 mesh (no devices needed)."""

    class FakeMesh:
        axis_names = ("data", "model")
        devices = np.empty((16, 16), object)

    return ShardingContext(FakeMesh())  # type: ignore[arg-type]


def test_divisible_dims_shard(ctx256):
    # llama3 wq: (d_model, heads, head_dim) = (16384, 128, 128)
    spec = resolve_pspec((16384, 128, 128), ("embed", "heads", "head_dim"),
                         ctx256)
    assert spec == P("data", "model")


def test_indivisible_heads_fall_back(ctx256):
    # llava: 56 heads % 16 != 0 -> replicate that dim, keep the others
    spec = resolve_pspec((7168, 56, 128), ("embed", "heads", "head_dim"),
                         ctx256)
    assert spec == P("data")


def test_vocab_fallback_granite(ctx256):
    # granite vocab 49155 is odd -> embedding replicates on vocab, shards d
    spec = resolve_pspec((49155, 2048), ("vocab", "embed"), ctx256)
    assert spec == P(None, "data")


def test_no_axis_reuse_within_tensor(ctx256):
    # both logical axes want 'model'; second must fall through
    spec = resolve_pspec((64, 64), ("seq", "vocab"), ctx256)
    flat = [a for e in spec if e for a in
            (e if isinstance(e, tuple) else (e,))]
    assert len(flat) == len(set(flat))
    assert spec[0] == "model"


@given(dim0=st.integers(1, 4096), dim1=st.integers(1, 4096))
@settings(max_examples=200, deadline=None)
def test_resolver_invariants(ctx256, dim0, dim1):
    """For any shape: placed axes divide their dims and are never reused."""
    spec = resolve_pspec((dim0, dim1), ("mlp", "heads"), ctx256)
    used = []
    for size, entry in zip((dim0, dim1), list(spec) + [None] * 2):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        prod = 1
        for a in axes:
            prod *= ctx256.axis_size(a)
            used.append(a)
        assert size % prod == 0
    assert len(used) == len(set(used))


def test_with_logical_identity_outside_context():
    import jax.numpy as jnp

    x = jnp.ones((4, 4))
    assert with_logical(x, ("batch", "seq")) is x


def test_with_logical_applies_constraint(single_mesh):
    import jax
    import jax.numpy as jnp

    def f(x):
        return with_logical(x, ("batch", None)) * 2

    with use_sharding(single_mesh):
        y = jax.jit(f)(jnp.ones((4, 4)))
    np.testing.assert_allclose(np.asarray(y), 2.0)


def test_multi_pod_axes_collapse(ctx256):
    """('pod','data') candidates collapse to the axes present in the mesh."""
    spec = resolve_pspec((256, 64), ("batch", None), ctx256)
    assert spec == P("data")  # no 'pod' axis in a single-pod mesh
