"""The refresh cell end to end at a test size, through the interpret path,
and the faults it must catch: every one turns ``correct`` false.

A refresh runs on one chip, so there is no exchange between chips to leave
out; the batch is the patch, and half of it left out is one of the faults.
"""
import re

import numpy as np
import pytest

from bench.tests.tiny import run

CELL = "g500-s21.refresh"


def _compiles_in_window(out: str) -> int:
    return int(re.search(r"compiles_in_window=(\d+)", out).group(1))


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_sound_program_is_correct(tmp_path, capsys, seed):
    res = run(tmp_path, CELL, seed=seed)
    out = capsys.readouterr().out
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"psi_node_rel", "read_mismatches"}
    assert res["checks"]["read_mismatches"]["value"] == 0
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert _compiles_in_window(out) == 0
    assert set(res["metrics"]) == {"setup_s", "refresh_s"}
    assert "patch_ms=" in out and "read_ms=" in out


def _not_correct(res, number):
    assert not res["correct"]
    check = res["checks"][number]
    assert check["value"] > check["limit"]


def test_patch_dropped(tmp_path, monkeypatch):
    """``update_activity`` takes the patch and drops it: the service
    re-solves the old activity and serves stale ψ."""
    from repro.core.incremental import PsiService

    def dropped(self, users, lam=None, mu=None, *, resolve=True):
        self._pending = True

    monkeypatch.setattr(PsiService, "update_activity", dropped)
    _not_correct(run(tmp_path, CELL), "psi_node_rel")


def test_half_of_the_patch_left_out(tmp_path, monkeypatch):
    from repro.core.incremental import PsiService
    update = PsiService.update_activity

    def halved(self, users, lam=None, mu=None, *, resolve=True):
        h = len(users) // 2
        update(self, users[:h], lam[:h], mu[:h], resolve=resolve)

    monkeypatch.setattr(PsiService, "update_activity", halved)
    _not_correct(run(tmp_path, CELL), "psi_node_rel")


def test_reads_from_the_previous_refresh(tmp_path, monkeypatch):
    """``top_k``, ``rank_of`` and ``scores_batch`` are answered from the
    ranking cache of the fixed point before the last re-solve, while
    ``scores`` serves the fresh ψ."""
    from repro.core.incremental import PsiService, RankedQueries
    resolve, read = PsiService._resolve, RankedQueries._read

    def keep_previous(self):
        self._previous = self._query()
        resolve(self)

    def stale_read(self, op, fn):
        if op == "scores" or getattr(self, "_previous", None) is None:
            return read(self, op, fn)
        return fn(self._previous)

    monkeypatch.setattr(PsiService, "_resolve", keep_previous)
    monkeypatch.setattr(RankedQueries, "_read", stale_read)
    res = run(tmp_path, CELL)
    _not_correct(res, "read_mismatches")
    assert res["failed"] >= 1


def test_top_k_shifted_by_one_rank(tmp_path, monkeypatch):
    from repro.core.incremental import RankingCache
    top_k = RankingCache.top_k

    def shifted(self, k):
        idx, vals = top_k(self, k + 1)
        return idx[1:], vals[1:]

    monkeypatch.setattr(RankingCache, "top_k", shifted)
    res = run(tmp_path, CELL)
    _not_correct(res, "read_mismatches")
    assert res["failed"] >= 1


def test_step_returning_its_state_unchanged(tmp_path, monkeypatch):
    import jax.numpy as jnp
    from repro.core import engine

    def frozen_step(interpret):
        return lambda args, s: (s, jnp.zeros((), s.dtype))

    monkeypatch.setattr(engine, "make_edge_tile_step", frozen_step)
    _not_correct(run(tmp_path, CELL), "psi_node_rel")


def test_score_altered_where_it_is_produced(tmp_path, monkeypatch):
    from repro.core.operators import PsiOperators
    epilogue = PsiOperators.psi_epilogue

    def altered(self, s):
        return epilogue(self, s).at[7].multiply(1.01)

    monkeypatch.setattr(PsiOperators, "psi_epilogue", altered)
    _not_correct(run(tmp_path, CELL), "psi_node_rel")


def test_rank_altered_where_it_is_produced(tmp_path, monkeypatch):
    from repro.core.incremental import RankingCache
    rank_of = RankingCache.rank_of

    def altered(self, users):
        ranks = np.array(rank_of(self, users))
        ranks[0] += 1
        return ranks

    monkeypatch.setattr(RankingCache, "rank_of", altered)
    _not_correct(run(tmp_path, CELL), "read_mismatches")


# --------------------------------------------------------------------- #
# The cold cell's path is the one it had before refreshes existed
# --------------------------------------------------------------------- #
class _Engine:
    def __init__(self, engine, calls):
        self._engine, self._calls = engine, calls

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def run(self, **kw):
        self._calls.append(("engine.run", kw))
        return self._engine.run(**kw)


class _Service:
    """The program's service, recording what the harness asks of it."""

    def __init__(self, svc):
        self._svc, self.touched, self.calls = svc, [], []

    def __getattr__(self, name):
        self.touched.append(name)
        if name == "engine":
            return _Engine(self._svc.engine, self.calls)
        return getattr(self._svc, name)


def test_cold_requests_and_spans_are_unchanged(tmp_path, monkeypatch):
    """Each cold request is one ``engine.run`` under a ``bench.solve``
    span; warm-up caps it at one iteration; no other call of the service
    is made (no patch, resolve or read)."""
    import jax
    from bench import harness

    spans = []
    annotation = jax.profiler.TraceAnnotation

    def recording(name, **kw):
        spans.append(name)
        return annotation(name, **kw)

    made = []

    def make(*args):
        made.append(_Service(harness.program_service(*args)))
        return made[-1]

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recording)
    res = run(tmp_path, "g500-s21.cold", make_service=make)
    assert res["correct"], res["checks"]
    svc, = made
    cfg_tol, cfg_max = svc._svc.tol, svc._svc.max_iter
    assert svc.calls[0] == ("engine.run", {"tol": cfg_tol, "max_iter": 1})
    assert svc.calls[1:] == [("engine.run",
                              {"tol": cfg_tol, "max_iter": cfg_max})] \
        * (len(svc.calls) - 1)
    assert len(svc.calls) == res["attempted"] + 1
    assert set(svc.touched) <= {"engine", "tol", "max_iter", "backend"}
    bench_spans = [s for s in spans if s.startswith("bench.")]
    assert bench_spans == ["bench.solve"] * len(svc.calls)
