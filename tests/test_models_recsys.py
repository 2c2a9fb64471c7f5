"""MIND + embedding substrate tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.models.recsys import mind
from repro.models.recsys.embedding import embedding_bag
from repro.train import adamw, constant_schedule


@pytest.fixture(scope="module")
def setup():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_arch("mind").config(reduced=True)
    params = mind.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B = 8
    batch = dict(
        hist_ids=jnp.asarray(rng.integers(0, cfg.n_items, (B, cfg.hist_len))),
        hist_mask=jnp.asarray(rng.random((B, cfg.hist_len)) > 0.2),
        profile_ids=jnp.asarray(rng.integers(0, cfg.n_profile, (B * 4,))),
        profile_bags=jnp.asarray(np.repeat(np.arange(B), 4)),
        pos_ids=jnp.asarray(rng.integers(0, cfg.n_items, (B,))),
        neg_ids=jnp.asarray(rng.integers(0, cfg.n_items, (B, cfg.n_neg))))
    return mesh, cfg, params, batch


def test_train_converges(setup):
    mesh, cfg, params, batch = setup
    opt = adamw(constant_schedule(1e-2))
    state = opt.init(params)

    @jax.jit
    def step(p, st, b):
        loss, g = jax.value_and_grad(mind.train_loss)(p, b, cfg, mesh)
        p, st = opt.apply(g, st, p)
        return p, st, loss

    losses = []
    for _ in range(15):
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(losses[-1])


def test_interests_shape_and_norm(setup):
    mesh, cfg, params, batch = setup
    u = mind.user_interests(params, batch["hist_ids"], batch["hist_mask"],
                            batch["profile_ids"], batch["profile_bags"],
                            cfg, mesh)
    assert u.shape == (8, cfg.n_interests, cfg.embed_dim)
    assert np.all(np.isfinite(np.asarray(u)))


def test_capsule_routing_mask(setup):
    """Fully-masked history must not produce NaNs (softmax over −inf)."""
    mesh, cfg, params, batch = setup
    mask = jnp.zeros_like(batch["hist_mask"])
    u = mind.user_interests(params, batch["hist_ids"], mask,
                            batch["profile_ids"], batch["profile_bags"],
                            cfg, mesh)
    assert np.all(np.isfinite(np.asarray(u)))


def test_retrieval_is_batched_dot(setup):
    mesh, cfg, params, batch = setup
    u = mind.user_interests(params, batch["hist_ids"], batch["hist_mask"],
                            batch["profile_ids"], batch["profile_bags"],
                            cfg, mesh)
    cands = jnp.arange(cfg.n_items, dtype=jnp.int32)
    scores = mind.retrieval_scores(params, u[0], cands, cfg, mesh)
    assert scores.shape == (cfg.n_items,)
    # max over interests: score >= each individual interest dot
    e = params["item_emb"]
    per = np.asarray(e @ np.asarray(u[0]).T)
    np.testing.assert_allclose(np.asarray(scores), per.max(axis=1),
                               rtol=1e-5, atol=1e-5)


def test_embedding_bag_modes():
    tbl = jnp.asarray(np.arange(40, dtype=np.float32).reshape(10, 4))
    ids = jnp.asarray([0, 1, 10, 5])       # 10 = sentinel
    bags = jnp.asarray([0, 0, 1, 2])
    s = embedding_bag(tbl, ids, bags, 3, mode="sum")
    m = embedding_bag(tbl, ids, bags, 3, mode="mean")
    np.testing.assert_allclose(np.asarray(s[0]),
                               np.asarray(tbl[0] + tbl[1]))
    np.testing.assert_allclose(np.asarray(m[0]),
                               np.asarray((tbl[0] + tbl[1]) / 2))
    np.testing.assert_allclose(np.asarray(s[1]), 0.0)   # sentinel-only bag
