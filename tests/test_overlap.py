"""Gradient-sync schedules, bucketing and microbatch accumulation (core.overlap)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.overlap import (accumulate_grads, grad_sync, make_buckets,
                                microbatch_split)


@given(sizes=st.lists(st.integers(1, 1000), min_size=1, max_size=20),
       k=st.integers(1, 8))
@settings(max_examples=100, deadline=None)
def test_make_buckets_partition(sizes, k):
    """Every leaf appears exactly once across buckets, order preserved inside."""
    tree = {f"w{i}": jnp.zeros((s,)) for i, s in enumerate(sizes)}
    buckets = make_buckets(tree, k)
    seen = [i for b in buckets for i, _ in b]
    assert sorted(seen) == list(range(len(sizes)))
    for b in buckets:
        idxs = [i for i, _ in b]
        assert idxs == sorted(idxs)


@given(sizes=st.lists(st.integers(100, 1000), min_size=4, max_size=16))
@settings(max_examples=50, deadline=None)
def test_make_buckets_balanced(sizes):
    """Greedy balance: max bucket <= sum/k + max leaf (classic LPT bound)."""
    k = 4
    tree = {f"w{i}": jnp.zeros((s,)) for i, s in enumerate(sizes)}
    buckets = make_buckets(tree, k)
    loads = [sum(int(l.size) for _, l in b) for b in buckets]
    assert max(loads) <= sum(sizes) / min(k, len(sizes)) + max(sizes)


def test_grad_sync_modes_identical_single_device(single_mesh):
    """On axis size 1 both schedules are the identity (psum over size-1)."""
    import functools

    from jax.sharding import PartitionSpec as P

    tree = {"a": jnp.arange(12.0).reshape(3, 4),
            "b": jnp.ones((7,)), "c": jnp.asarray(2.0)}

    for mode in ("two_phase", "hdot"):
        f = jax.jit(jax.shard_map(
            functools.partial(grad_sync, axes="data", mode=mode),
            mesh=single_mesh, in_specs=(P(),), out_specs=P()))
        out = f(tree)
        for k in tree:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(tree[k]), rtol=1e-6)


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_accumulate_grads_linearity(steps):
    """Accumulated mean-loss grads == full-batch grads for a loss that is a
    mean over examples (linearity of grad in the batch)."""
    w = jnp.asarray([1.0, -2.0, 0.5])

    def loss_fn(w, batch):
        pred = batch["x"] @ w
        return jnp.mean((pred - batch["y"]) ** 2)

    k = jax.random.PRNGKey(0)
    batch = {"x": jax.random.normal(k, (8, 3)),
             "y": jax.random.normal(jax.random.fold_in(k, 1), (8,))}

    def lg(w, b):
        return jax.value_and_grad(loss_fn)(w, b)

    loss_a, g_a = accumulate_grads(lg, w, batch, steps)
    loss_f, g_f = lg(w, batch)
    np.testing.assert_allclose(float(loss_a), float(loss_f), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_a), np.asarray(g_f),
                               rtol=1e-5, atol=1e-6)


def test_microbatch_split_roundtrip():
    batch = {"tokens": jnp.arange(24).reshape(8, 3)}
    mb = microbatch_split(batch, 4)
    assert mb["tokens"].shape == (4, 2, 3)
    np.testing.assert_array_equal(
        np.asarray(mb["tokens"].reshape(8, 3)), np.asarray(batch["tokens"]))


def test_microbatch_split_requires_divisibility():
    """A real ValueError naming the sizes — not a bare assert that vanishes
    under `python -O` into a shapeless reshape error."""
    with pytest.raises(ValueError, match="batch 6.*accum steps 4"):
        microbatch_split({"x": jnp.zeros((6, 2))}, 4)


# ------------------------------------------------- zero-copy bucketed sync
def _mixed_tree():
    """Integer-valued mixed-dtype gradients: bf16 sums are exact, so the
    schedules must agree bit-for-bit."""
    k = jax.random.PRNGKey(0)
    return {
        "emb": jax.random.randint(k, (16, 8), -4, 5).astype(jnp.bfloat16),
        "w1": jax.random.randint(jax.random.fold_in(k, 1), (32,), -4, 5
                                 ).astype(jnp.float32),
        "w2": jax.random.randint(jax.random.fold_in(k, 2), (4, 4), -4, 5
                                 ).astype(jnp.float16),
        "b": jnp.asarray(3.0),
    }


def _sync_fn(mode, mesh):
    import functools

    from jax.sharding import PartitionSpec as P

    return jax.jit(jax.shard_map(
        functools.partial(grad_sync, axes="data", mode=mode, num_buckets=2),
        mesh=mesh, in_specs=(P(),), out_specs=P()))


def test_grad_sync_hdot_mixed_dtype_matches_two_phase(single_mesh):
    tree = _mixed_tree()
    out_hd = _sync_fn("hdot", single_mesh)(tree)
    out_tp = _sync_fn("two_phase", single_mesh)(tree)
    for k in tree:
        assert out_hd[k].dtype == tree[k].dtype, k   # no dtype round-trip
        np.testing.assert_array_equal(
            np.asarray(out_hd[k], np.float32), np.asarray(out_tp[k], np.float32))


def test_grad_sync_hdot_is_zero_copy(single_mesh):
    """The structural claim of the optimization: the hdot sync path stages no
    concatenated flat buffer (the two-phase baseline does)."""
    tree = _mixed_tree()
    hlo_hd = _sync_fn("hdot", single_mesh).lower(tree).as_text()
    hlo_tp = _sync_fn("two_phase", single_mesh).lower(tree).as_text()
    assert "concatenate" not in hlo_hd
    assert "concatenate" in hlo_tp
