"""The compared numbers on hand-made requests."""
import numpy as np
import pytest

from bench import checks
from bench.harness import Request


class _Ref:
    def __init__(self, psi):
        self._psi = psi

    def psi(self):
        return self._psi


def _requests(psis, iterations):
    return [Request(index=i, timed=i > 0, psi=np.asarray(p, np.float32),
                    iterations=t)
            for i, (p, t) in enumerate(zip(psis, iterations))]


REF = np.array([0.1, 0.2, 0.4], np.float64)


def test_sound_solves_read_their_rounding():
    reqs = _requests([REF] * 4, [1, 9, 9, 9])
    out = checks.compare(_Ref(REF), reqs, sample_size=1, seed=3)
    assert out["solve_mismatches"] == 0
    assert out["psi_node_rel"] < 1e-7


@pytest.mark.parametrize("bad", ["iterations", "psi"])
def test_a_solve_unlike_the_first_is_a_mismatch(bad):
    psis = [REF] * 4
    its = [1, 9, 9, 9]
    if bad == "psi":
        psis = psis[:2] + [REF * (1 + 1e-6)] + psis[3:]
    else:
        its = [1, 9, 8, 9]
    out = checks.compare(_Ref(REF), _requests(psis, its), sample_size=1,
                         seed=3)
    assert out["solve_mismatches"] == 1


def test_the_last_solve_is_always_compared():
    psis = [REF, REF, REF, REF * 1.5]
    out = checks.compare(_Ref(REF), _requests(psis, [1, 9, 9, 9]),
                         sample_size=1, seed=11)
    assert out["psi_node_rel"] == pytest.approx(0.5)


@pytest.mark.parametrize("psi", [[0.1, 0.2], [0.1, np.nan, 0.4]])
def test_a_wrong_shape_or_a_nan_reads_infinite(psi):
    assert checks.node_rel(np.asarray(psi), REF) == float("inf")


def test_sample_keeps_the_last_and_follows_the_seed():
    idx = list(range(1, 40))
    a = checks.sample(idx, 5, seed=2**31 + 9)
    assert 39 in a and len(a) == 5
    assert a == checks.sample(idx, 5, seed=2**31 + 9)


# --------------------------------------------------------------------- #
# Refreshes: each sampled one against the reference of its own activity
# --------------------------------------------------------------------- #
class _ActivityRef:
    """ψ = λ / (λ + μ): a reference that depends on the activity only."""

    def __init__(self, lam, mu):
        self.lam, self.mu = lam, mu

    def psi(self, lam, mu):
        return lam / (lam + mu)


def _served(psi, q, fault=None):
    psi = np.asarray(psi, np.float32)
    order = np.argsort(-psi, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    ids = order[:q.top_k]
    answers = {"top_k": (ids, psi[ids]), "rank_of": rank[q.rank_users],
               "scores_batch": psi[q.score_users]}
    if fault == "top_k":
        answers["top_k"] = (order[1:q.top_k + 1], psi[order[1:q.top_k + 1]])
    elif fault == "rank_of":
        answers["rank_of"] = answers["rank_of"] + 1
    elif fault == "scores_batch":
        answers["scores_batch"] = psi[q.score_users + 1]
    return psi, answers


def _refreshes(n_req=6, stale=None, fault=None, faulty=()):
    from bench.traffic import Refresh
    n = 50
    gen = np.random.default_rng(0)
    lam = gen.uniform(0.1, 1, n)
    mu = gen.uniform(0.1, 1, n)
    ref = _ActivityRef(lam.copy(), mu.copy())
    reqs, prev = [], lam / (lam + mu)
    for i in range(n_req):
        q = Refresh(users=gen.choice(n, 5, replace=False),
                    lam=gen.uniform(0.1, 1, 5), mu=gen.uniform(0.1, 1, 5),
                    top_k=4, rank_users=gen.integers(0, n - 1, 7),
                    score_users=gen.integers(0, n - 1, 7))
        lam[q.users], mu[q.users] = q.lam, q.mu
        fresh = lam / (lam + mu)
        psi, answers = _served(prev if i == stale else fresh, q,
                               fault if i in faulty else None)
        reqs.append(Request(index=i, timed=i > 0, psi=psi, refresh=q,
                            answers=answers, iterations=9))
        prev = fresh
    return ref, reqs


def test_sound_refreshes_read_their_rounding():
    ref, reqs = _refreshes()
    out = checks.compare_refresh(ref, reqs, sample_size=10, seed=1)
    assert out["read_mismatches"] == 0 and out["failed"] == 0
    assert out["psi_node_rel"] < 1e-7


def test_a_stale_refresh_is_caught():
    """The last refresh is always compared; serving the ψ before its
    patch reads as the patch's effect, far above rounding."""
    ref, reqs = _refreshes(stale=5)
    out = checks.compare_refresh(ref, reqs, sample_size=1, seed=1)
    assert out["psi_node_rel"] > 1e-3
    assert out["read_mismatches"] == 0


@pytest.mark.parametrize("fault", ["top_k", "rank_of", "scores_batch"])
def test_a_wrong_read_is_a_mismatch(fault):
    ref, reqs = _refreshes(fault=fault, faulty=(4, 5))
    out = checks.compare_refresh(ref, reqs, sample_size=1, seed=1)
    assert out["read_mismatches"] == 1 and out["failed"] == 1
    out = checks.compare_refresh(ref, reqs, sample_size=10, seed=1)
    assert out["read_mismatches"] == 2 and out["failed"] == 2


def test_warm_up_patches_are_replayed():
    """A refresh sent before the window (not timed) still moves the
    activity every later refresh is compared under."""
    ref, reqs = _refreshes()
    out = checks.compare_refresh(ref, reqs[1:], sample_size=10, seed=1)
    assert out["psi_node_rel"] > 1e-3
