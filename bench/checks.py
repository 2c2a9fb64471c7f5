"""What decides ``correct``: the served answers against the float64 reference.

Once the window has closed and the program's state is freed, a
:class:`~bench.reference.Reference` solves ψ of the same deployment in
float64. Two numbers are compared, each against the cell's limit in
``bench/limits/<cell>.json``:

* ``psi_node_rel`` — the widest relative gap ``|ψ − ψ_ref| / ψ_ref`` of any
  user at a seeded sample of the window's solves, the last one always among
  them (ψ_ref > 0 for every user: d = λ/(λ+μ) > 0);
* ``solve_mismatches`` — solves of the window that took another number of
  iterations than its first, or served another ψ (limit 0: in a cold mix
  every solve is the same work, so each is compared through the first).

A refresh mix (:func:`compare_refresh`) changes the activity with every
request, so each sampled refresh is compared with the reference of the
activity as it stood after that refresh's patch, every patch sent before it
replayed in float64 onto the deployment's own (λ, μ):

* ``psi_node_rel`` — as above, at the sampled refreshes;
* ``read_mismatches`` — reads of the sampled refreshes whose answer differs
  from plain NumPy on the ψ that refresh served (limit 0): ``top_k``'s
  values must be the k largest of ψ and ψ at its ids must be those values;
  ``rank_of`` must be the 0-based rank in a stable descending ``argsort``;
  ``scores_batch`` must be ψ at its users.
"""
from __future__ import annotations

import numpy as np

from bench.graphs import rng


def sample(indices: list[int], k: int, seed: int) -> set[int]:
    """``k`` request indices drawn from the seed, always with the last."""
    if not indices:
        return set()
    rest = indices[:-1]
    pick = rng(seed, "check").choice(len(rest), min(k - 1, len(rest)),
                                     replace=False) if k > 1 and rest else []
    return {rest[i] for i in pick} | {indices[-1]}


def node_rel(psi: np.ndarray, ref: np.ndarray) -> float:
    psi = np.asarray(psi, np.float64)
    if psi.shape != ref.shape or not np.all(np.isfinite(psi)):
        return float("inf")
    return float(np.max(np.abs(psi - ref) / ref))


def compare(reference, requests, *, sample_size: int, seed: int
            ) -> dict[str, float]:
    """The compared numbers of ``requests``, every one the service was sent
    in order, of which the timed ones are the window's, and ``failed``:
    the solves that mismatched."""
    done = [r for r in requests if r.timed]
    picked = sample([r.index for r in done], sample_size, seed)
    psi_ref = reference.psi()
    worst = max(node_rel(r.psi, psi_ref) for r in done if r.index in picked)
    first = done[0]
    mismatches = sum(int(r.iterations != first.iterations
                         or not np.array_equal(r.psi, first.psi))
                     for r in done[1:])
    return {"psi_node_rel": worst, "solve_mismatches": mismatches,
            "failed": mismatches}


def read_mismatches(psi: np.ndarray, refresh, answers: dict) -> int:
    """How many of one refresh's reads differ from NumPy on its served ψ."""
    psi = np.asarray(psi)
    ids, vals = answers["top_k"]
    k = min(refresh.top_k, psi.size)
    top = np.sort(psi)[::-1][:k]
    bad_top = not (ids.shape == vals.shape == top.shape
                   and np.array_equal(vals, top)
                   and np.array_equal(psi[ids], vals))
    order = np.argsort(-psi, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(order.size)
    bad_rank = not np.array_equal(answers["rank_of"],
                                  rank[refresh.rank_users])
    bad_scores = not np.array_equal(answers["scores_batch"],
                                    psi[refresh.score_users])
    return int(bad_top) + int(bad_rank) + int(bad_scores)


def compare_refresh(reference, requests, *, sample_size: int, seed: int
                    ) -> dict[str, float]:
    """The compared numbers of a refresh mix's ``requests``, every one the
    service was sent in order, and ``failed``: sampled refreshes with a
    mismatched read."""
    done = [r.index for r in requests if r.timed]
    picked = sample(done, sample_size, seed)
    lam = np.array(reference.lam, np.float64)
    mu = np.array(reference.mu, np.float64)
    worst, reads, failed = 0.0, 0, 0
    for r in requests:
        q = r.refresh
        lam[q.users] = q.lam
        mu[q.users] = q.mu
        if r.index not in picked:
            continue
        worst = max(worst, node_rel(r.psi, reference.psi(lam, mu)))
        bad = read_mismatches(r.psi, q, r.answers)
        reads += bad
        failed += int(bad > 0)
    return {"psi_node_rel": worst, "read_mismatches": reads,
            "failed": failed}
