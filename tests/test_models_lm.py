"""Per-arch LM smoke tests (reduced configs) + attention path parity."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.models.transformer import (LMConfig, MoECfg, init_params, forward,
                                      make_train_step, make_prefill,
                                      make_decode_step, init_cache,
                                      count_params)
from repro.models.transformer.attention import _blocked, _banded, _dense
from repro.train import adamw, constant_schedule

LM_ARCHS = ["tinyllama-1.1b", "yi-9b", "nemotron-4-340b", "mixtral-8x22b",
            "mixtral-8x7b"]


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_reduced_train_step(arch, mesh):
    """One forward/train step on CPU: output shapes + no NaNs."""
    cfg = get_arch(arch).config(reduced=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(constant_schedule(1e-3))
    state = opt.init(params)
    step = jax.jit(make_train_step(cfg, mesh, opt))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 17)))
    batch = dict(tokens=toks[:, :-1], labels=toks[:, 1:])
    losses = []
    for _ in range(5):
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mixtral-8x7b"])
def test_prefill_then_decode_matches_forward(arch, mesh):
    cfg = get_arch(arch).config(reduced=True)
    if cfg.moe:  # avoid capacity drops for exact parity
        cfg = type(cfg)(**{**cfg.__dict__,
                           "moe": MoECfg(cfg.moe.n_experts, cfg.moe.top_k,
                                         capacity_factor=8.0)})
    params = init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    S = 12
    seq = jnp.asarray(rng.integers(0, cfg.vocab, (2, S + 4)))
    logits_full = forward(params, seq, cfg, mesh)
    prefill = jax.jit(make_prefill(cfg, mesh, max_len=S + 4))
    decode = jax.jit(make_decode_step(cfg, mesh))
    cache, lg = prefill(params, seq[:, :S])
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_full[:, S - 1]),
                               rtol=2e-4, atol=2e-4)
    for t in range(S, S + 4):
        cache, lg = decode(params, cache, seq[:, t])
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_param_count_formula():
    cfg = get_arch("tinyllama-1.1b").config()
    n = count_params(cfg)
    assert 1.0e9 < n < 1.25e9          # ~1.1B
    cfg = get_arch("mixtral-8x7b").config()
    assert 44e9 < count_params(cfg) < 49e9   # ~46.7B total


def test_blocked_attention_equals_dense():
    rng = np.random.default_rng(0)
    b, s, hq, hkv, dh = 2, 512, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(b, s, hkv, hq // hkv, dh))
                    .astype("float32"))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype("float32"))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    dense = _dense(q, k, v, pos, pos, None, None)
    blocked = _blocked(q, k, v, pos, pos, None, 128, 64)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_banded_swa_equals_dense_window():
    rng = np.random.default_rng(1)
    b, s, hkv, g, dh, w = 1, 1024, 2, 2, 16, 64
    q = jnp.asarray(rng.normal(size=(b, s, hkv, g, dh)).astype("float32"))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype("float32"))
    v = jnp.asarray(rng.normal(size=(b, s, hkv, dh)).astype("float32"))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    dense = _dense(q, k, v, pos, pos, w, None)
    banded = _banded(q, k, v, pos, pos, w, 128)
    np.testing.assert_allclose(np.asarray(banded), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_moe_capacity_drops_tokens():
    """cap < load ⇒ overflow tokens are dropped (GShard semantics)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = LMConfig(name="m", n_layers=1, d_model=32, n_heads=2, n_kv_heads=1,
                   d_ff=64, vocab=64, moe=MoECfg(2, 2, capacity_factor=0.1),
                   dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 16)))
    out = forward(params, toks, cfg, mesh)
    assert np.all(np.isfinite(np.asarray(out)))
