"""Data pipeline determinism/sharding + serving runtime behaviour."""
from __future__ import annotations

import jax
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data.pipeline import SyntheticLMDataset


def _ds(**kw):
    d = dict(vocab_size=97, seq_len=16, global_batch=8, seed=5)
    d.update(kw)
    return SyntheticLMDataset(**d)


def test_batches_deterministic():
    a = _ds().batch_at(3)
    b = _ds().batch_at(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(_ds().batch_at(4)["tokens"], a["tokens"])


def test_targets_are_next_tokens():
    b = _ds(noise=0.0, a=31).batch_at(0)
    # noiseless: affine chain t+1 = (a*t + b) % V
    nxt = (b["tokens"].astype(np.int64) * 31 + 7) % 97
    np.testing.assert_array_equal(b["targets"], nxt)


@given(num_hosts=st.sampled_from([1, 2, 4, 8]), step=st.integers(0, 50))
@settings(max_examples=25, deadline=None)
def test_host_slices_tile_global_batch(num_hosts, step):
    ds = _ds()
    full = ds.batch_at(step)
    parts = [ds.host_slice(step, h, num_hosts) for h in range(num_hosts)]
    np.testing.assert_array_equal(
        np.concatenate([p["tokens"] for p in parts], axis=0), full["tokens"])


def test_state_roundtrip():
    ds = _ds()
    st8 = ds.state(8)
    assert SyntheticLMDataset.resume_step(st8) == 8


# ------------------------------------------------------------------ server
def test_server_waves_and_lengths():
    from repro.config.registry import get_arch
    from repro.models.model import ModelOptions, build_model
    from repro.runtime.server import BatchServer, Request

    cfg = get_arch("internlm2-1.8b").reduced()
    import dataclasses

    cfg = dataclasses.replace(cfg, num_layers=2)
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    server = BatchServer(model, params, slots=2, max_len=64)
    rng = np.random.default_rng(0)
    for i in range(5):
        server.submit(Request(prompt=rng.integers(1, 100, 6).tolist(),
                              max_new_tokens=4 + i))
    served = server.run_all()
    assert len(served) == 5
    for i, r in enumerate(served):
        assert len(r.output) == 4 + i
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_server_greedy_matches_manual_decode():
    """Server output must equal hand-rolled prefill+argmax decode."""
    import dataclasses
    import jax.numpy as jnp

    from repro.config.registry import get_arch
    from repro.models.model import ModelOptions, build_model
    from repro.runtime.server import BatchServer, Request

    cfg = dataclasses.replace(get_arch("internlm2-1.8b").reduced(),
                              num_layers=2)
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    prompt = [5, 17, 29, 3]
    n_new = 5

    server = BatchServer(model, params, slots=1, max_len=64)
    server.submit(Request(prompt=prompt, max_new_tokens=n_new))
    out_server = server.run_all()[0].output

    toks = jnp.asarray([prompt], jnp.int32)
    logits, caches = model.prefill(params, {"tokens": toks}, max_len=64)
    out_manual = []
    tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
    pos = len(prompt)
    for _ in range(n_new):
        out_manual.append(int(tok[0, 0]))
        logits, caches = model.decode_step(params, tok, caches,
                                           jnp.asarray(pos, jnp.int32))
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        pos += 1
    assert out_server == out_manual


def test_server_eos_stops_early():
    import dataclasses

    from repro.config.registry import get_arch
    from repro.models.model import ModelOptions, build_model
    from repro.runtime.server import BatchServer, Request

    cfg = dataclasses.replace(get_arch("internlm2-1.8b").reduced(),
                              num_layers=1)
    model = build_model(cfg, ModelOptions(attn_impl="dense"))
    params = model.init(jax.random.PRNGKey(0))
    server = BatchServer(model, params, slots=1, max_len=64)
    # discover the greedy first token, then use it as EOS: output length 1
    server.submit(Request(prompt=[1, 2, 3], max_new_tokens=8))
    first = server.run_all()[0].output[0]
    server.submit(Request(prompt=[1, 2, 3], max_new_tokens=8, eos_id=first))
    out = server.run_all()[0].output
    assert out == [first]
