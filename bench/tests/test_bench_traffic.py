"""The traffic generator's mixes: what it accepts and what it refuses, and
the refreshes it draws."""
import json

import numpy as np
import pytest

from bench import traffic
from bench.harness import BENCH

ACTIVITY = {"kind": "uniform", "low": 1e-3, "high": 1.0}


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_cold_mix_is_valid():
    mix = traffic.validate(_mix("cold"))
    assert mix["solve"] == "cold" and mix["check_sample"] >= 1
    assert traffic.kind(mix) == "cold"


@pytest.mark.parametrize("bad", [
    {"solve": "warm"},
    {"clients": 2},
    {"loop": "open"},
    {"warmup_requests": 0},
    {"trace_requests": 1.5},
])
def test_invalid_mixes_are_refused(bad):
    with pytest.raises(ValueError):
        traffic.validate(dict(_mix("cold"), **bad))


def test_refresh_mix_is_valid():
    mix = traffic.validate(_mix("refresh"))
    assert traffic.kind(mix) == "refresh"
    assert mix["refresh"]["patch_users"] == 1024
    assert mix["refresh"]["reads"] == {"top_k": 100, "rank_of": 1000,
                                       "scores_batch": 1000}
    assert mix["trace_requests"] == 3 and mix["check_sample"] == 2


def _refresh(**change):
    mix = _mix("refresh")
    r = dict(mix["refresh"], reads=dict(mix["refresh"]["reads"]))
    for key, value in change.items():
        if key in traffic.READS:
            r["reads"][key] = value
        elif value is None:
            r.pop(key)
        else:
            r[key] = value
    return dict(mix, refresh=r)


@pytest.mark.parametrize("mix", [
    _refresh(patch_users=None),
    _refresh(patch_users=0),
    _refresh(patch_users=-4),
    _refresh(patch_users=True),
    _refresh(patch_zipf_s=0),
    _refresh(top_k=0),
    _refresh(rank_of=0),
    _refresh(scores_batch=0),
    dict(_mix("refresh"), clients=2),
    dict(_mix("refresh"), solve="cold"),
], ids=["patch_users-missing", "patch_users-0", "patch_users-negative",
        "patch_users-bool", "zipf-0", "top_k-0", "rank_of-0",
        "scores_batch-0", "clients-2", "cold-and-refresh"])
def test_invalid_refresh_mixes_are_refused(mix):
    with pytest.raises(ValueError):
        traffic.validate(mix)


def _gen(seed, n=5000, **change):
    mix = traffic.validate(_refresh(**change))
    return traffic.RefreshTraffic(mix, n, ACTIVITY, structure_seed=3,
                                  seed=seed)


def test_refreshes_have_fixed_sizes_and_follow_the_seed():
    gen = _gen(2**33 + 1)
    q = gen.request(0)
    assert np.unique(q.users).size == q.users.size == 1024
    assert q.lam.shape == q.mu.shape == (1024,)
    assert 1e-3 <= min(q.lam.min(), q.mu.min())
    assert max(q.lam.max(), q.mu.max()) < 1.0
    assert q.top_k == 100
    assert q.rank_users.shape == q.score_users.shape == (1000,)
    again = _gen(2**33 + 1).request(0)
    assert np.array_equal(again.users, q.users)
    assert np.array_equal(again.score_users, q.score_users)
    assert not np.array_equal(gen.request(1).users, q.users)
    # another seed sends the same patch, and reads about other users
    other = _gen(2**33 + 2).request(0)
    assert np.array_equal(other.users, q.users)
    assert np.array_equal(other.lam, q.lam) and np.array_equal(other.mu, q.mu)
    assert not np.array_equal(other.rank_users, q.rank_users)
    assert not np.array_equal(other.score_users, q.score_users)


def test_patched_users_are_skewed_toward_a_fixed_order():
    """The most popular user of the structure seed's order is patched in
    nearly every refresh, whatever the run's seed; a user far down the
    order only now and then."""
    top = _gen(1).popular[0]
    tail = _gen(1).popular[-1]
    for seed in (1, 2**31 + 9):
        gen = _gen(seed, patch_users=64)
        hits = [gen.request(i).users for i in range(50)]
        assert sum(top in u for u in hits) >= 45
        assert sum(tail in u for u in hits) <= 5


def test_more_patched_users_than_users_is_refused():
    with pytest.raises(ValueError, match="patch_users"):
        _gen(1, n=100)
