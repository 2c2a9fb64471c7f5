"""The one general traffic generator: a mix file's parameters → requests.

A mix (``bench/traffic/<mix>.json``) describes the requests of a closed loop
with a single client. The kinds of request it can send:

* ``"solve": "cold"`` — solve the standing deployment from c, with no warm
  start, and copy ψ to the host: the path of ``PsiService``'s first solve.
  Every such request is the same work, so it carries no parameters of its
  own.
* ``"refresh": {...}`` — one activity refresh through ``PsiService``'s
  public API: patch (λ, μ) of ``patch_users`` distinct users, re-solve warm
  from the previous fixed point, then the reads of ``reads`` in order
  (``top_k``, ``rank_of``, ``scores_batch``). The users are drawn with
  Zipf(``patch_zipf_s``) weights over a fixed random popularity order
  (:class:`RefreshTraffic`).

``warmup_requests`` of them warm up before the window, ``trace_requests``
more are traced after it, and ``check_sample`` of the window's are compared
with the reference (the last one always among them).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.graphs import rng

SOLVES = ("cold",)
COUNTS = ("warmup_requests", "trace_requests", "check_sample")
READS = ("top_k", "rank_of", "scores_batch")


def _count(value) -> bool:
    return isinstance(value, int) and not isinstance(value, bool) \
        and value >= 1


def kind(mix: dict) -> str:
    """``"refresh"`` or ``"cold"``."""
    return "refresh" if "refresh" in mix else "cold"


def validate(mix: dict) -> dict:
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError("only a closed loop with one client is supported")
    if "refresh" in mix:
        if "solve" in mix:
            raise ValueError("a mix sends cold solves or refreshes, not both")
        _validate_refresh(mix["refresh"])
    elif mix.get("solve") not in SOLVES:
        raise ValueError(f"solve must be one of {SOLVES}")
    for key in COUNTS:
        if not _count(mix.get(key)):
            raise ValueError(f"{key} must be a whole number >= 1")
    return mix


def _validate_refresh(r: dict) -> None:
    if not _count(r.get("patch_users")):
        raise ValueError("refresh.patch_users must be a whole number >= 1")
    s = r.get("patch_zipf_s")
    if isinstance(s, bool) or not isinstance(s, (int, float)) or s <= 0:
        raise ValueError("refresh.patch_zipf_s must be a number > 0")
    reads = r.get("reads")
    if not isinstance(reads, dict) or set(reads) != set(READS):
        raise ValueError(f"refresh.reads must give exactly {READS}")
    for key in READS:
        if not _count(reads[key]):
            raise ValueError(f"refresh.reads.{key} must be a whole number "
                             ">= 1")


@dataclasses.dataclass
class Refresh:
    """One refresh's parameters."""

    users: np.ndarray          # distinct users whose (λ, μ) are patched
    lam: np.ndarray
    mu: np.ndarray
    top_k: int
    rank_users: np.ndarray
    score_users: np.ndarray


class RefreshTraffic:
    """The refreshes of a mix on one deployment of ``n`` users.

    Every seed sends the same patches: the popularity order (which user is
    the most patched, which the next) and refresh ``index``'s patch — its
    users and their new (λ, μ) — are drawn from the configuration's
    ``structure_seed``, because which hubs a patch touches sets how many
    iterations its warm re-solve takes. The run's ``seed`` draws the users
    each refresh's reads ask about (and, in the deployment, the order of the
    edge list)."""

    def __init__(self, mix: dict, n: int, activity: dict,
                 structure_seed: int, seed: int):
        r = mix["refresh"]
        if r["patch_users"] > n:
            raise ValueError(f"patch_users {r['patch_users']} > {n} users")
        if activity["kind"] != "uniform":
            raise ValueError(f"unknown activity profile {activity['kind']!r}")
        self.n, self.seed, self.structure_seed = n, seed, structure_seed
        self.k = r["patch_users"]
        self.reads = r["reads"]
        self.low, self.high = activity["low"], activity["high"]
        self.popular = rng(structure_seed, "popularity").permutation(n)
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(r["patch_zipf_s"])
        self.cdf = np.cumsum(w) / w.sum()

    def _zipf_distinct(self, gen: np.random.Generator) -> np.ndarray:
        """``k`` distinct popularity ranks, in the order first drawn."""
        drawn = np.empty(0, np.int64)
        while True:
            u = gen.random(2 * self.k)
            ranks = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                               self.n - 1)
            drawn = np.concatenate([drawn, ranks])
            uniq, first = np.unique(drawn, return_index=True)
            if uniq.size >= self.k:
                return drawn[np.sort(first)[:self.k]]

    def request(self, index: int) -> Refresh:
        patch = rng(self.structure_seed, f"patch.{index}")
        users = self.popular[self._zipf_distinct(patch)]
        lam = patch.uniform(self.low, self.high, self.k)
        mu = patch.uniform(self.low, self.high, self.k)
        reads = rng(self.seed, f"refresh.{index}")
        return Refresh(users=users, lam=lam, mu=mu,
                       top_k=self.reads["top_k"],
                       rank_users=reads.integers(0, self.n,
                                                 self.reads["rank_of"]),
                       score_users=reads.integers(0, self.n,
                                                  self.reads["scores_batch"]))
