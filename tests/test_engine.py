"""Unified PsiEngine abstraction: backend parity, delta rebuilds, serving."""
import numpy as np
import pytest

import repro.core.operators as operators_mod
from repro.graphs import clustered_blocks, erdos_renyi, powerlaw_configuration
from repro.core import (Activity, heterogeneous, homogeneous, exact_psi,
                        make_engine, available_backends, ConvergenceCriterion,
                        PsiService, HostOperators, build_operators, power_psi)
from repro.graphs.structure import Graph

BACKENDS = ["reference", "pallas", "auto", "accelerated", "distributed",
            "async", "push"]


@pytest.fixture(scope="module")
def platform():
    g = powerlaw_configuration(500, 3000, seed=42)
    act = heterogeneous(g.n, seed=43)
    psi_true, s_true = exact_psi(g, act)
    return g, act, psi_true, s_true


# --------------------------------------------------------------------- #
# Parity: all registered backends agree with the exact solver
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_parity_with_exact(platform, backend):
    g, act, psi_true, _ = platform
    eng = make_engine(backend, graph=g, activity=act)
    res = eng.run(tol=1e-10)
    assert bool(res.converged)
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_warm_start_path(platform, backend):
    """s0 threading: a converged s* re-converges immediately and exactly."""
    g, act, psi_true, _ = platform
    eng = make_engine(backend, graph=g, activity=act)
    cold = eng.run(tol=1e-10)
    warm = eng.run(tol=1e-10, s0=cold.s)
    assert int(warm.iterations) < int(cold.iterations)
    assert np.abs(np.asarray(warm.psi) - psi_true).max() <= 1e-6


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_step_protocol(platform, backend):
    """prepare → repeated step drives the gap down under the shared rule."""
    g, act, _, _ = platform
    eng = make_engine(backend, graph=g, activity=act)
    state = eng.prepare(g, act)
    for _ in range(5):
        state = eng.step(state)
    assert state.t == 5
    first_gap = state.gap
    for _ in range(10):
        state = eng.step(state)
    assert state.gap < first_gap


def test_epilogue_matches_reference(platform):
    g, act, _, s_true = platform
    ref = make_engine("reference", graph=g, activity=act)
    pal = make_engine("pallas", graph=g, activity=act)
    psi_r = np.asarray(ref.epilogue(s_true.astype(np.float32)))
    psi_p = np.asarray(pal.epilogue(s_true.astype(np.float32)))
    np.testing.assert_allclose(psi_r, psi_p, rtol=1e-6, atol=1e-10)


def test_make_engine_rejects_unknown():
    with pytest.raises(ValueError, match="unknown backend"):
        make_engine("nope")
    assert set(BACKENDS) <= set(available_backends())


def test_criterion_validation():
    with pytest.raises(ValueError, match="unknown norm"):
        ConvergenceCriterion(norm="l7")
    with pytest.raises(ValueError, match="l1"):
        make_engine("pallas", criterion=ConvergenceCriterion(norm="l2"))


def test_reference_engine_matches_power_psi(platform):
    """The refactor is behavior-preserving vs the historical entry point."""
    g, act, _, _ = platform
    eng = make_engine("reference", graph=g, activity=act)
    res_new = eng.run(tol=1e-9)
    res_old = power_psi(build_operators(g, act), tol=1e-9)
    np.testing.assert_allclose(np.asarray(res_new.psi),
                               np.asarray(res_old.psi), rtol=1e-6, atol=1e-12)
    # host operators accumulate in float64 before the device cast, so the
    # tol crossing may land ±1 iteration from the all-float32 build
    assert abs(int(res_new.iterations) - int(res_old.iterations)) <= 1


# --------------------------------------------------------------------- #
# HostOperators: the O(Δ) patch layer
# --------------------------------------------------------------------- #
def test_host_operators_patch_activity_matches_rebuild(platform):
    g, act, _, _ = platform
    hs = HostOperators.from_graph(g, act)
    users = np.asarray([3, 99, 3])                # dup: last write wins
    hs.patch_activity(users, lam=np.asarray([2.0, 0.5, 4.0]))
    lam2 = act.lam.copy()
    lam2[3], lam2[99] = 4.0, 0.5
    fresh = HostOperators.from_graph(g, Activity(lam2, act.mu))
    np.testing.assert_allclose(hs.w, fresh.w, rtol=1e-12)
    np.testing.assert_allclose(hs.row_lam, fresh.row_lam, rtol=1e-12)
    assert abs(hs.b_norm - fresh.b_norm) < 1e-12


def test_host_operators_patch_edges_matches_rebuild(platform):
    g, act, _, _ = platform
    hs = HostOperators.from_graph(g, act)
    new_src = np.asarray([0, 1, 2, 2, 0])
    new_dst = np.asarray([5, 6, 7, 2, 5])         # one self-loop, one dup
    kept_s, kept_d = hs.patch_edges(new_src, new_dst)
    assert kept_s.size <= 4
    g2 = Graph(g.n, np.concatenate([g.src, new_src]),
               np.concatenate([g.dst, new_dst])).dedup()
    fresh = HostOperators.from_graph(g2, act)
    assert hs.m == fresh.m
    np.testing.assert_allclose(np.sort(hs.w), np.sort(fresh.w), rtol=1e-12)
    np.testing.assert_allclose(hs.w, fresh.w, rtol=1e-12)
    # sorted views stay sorted (segment_sum precondition)
    assert np.all(np.diff(hs.dst_by_dst) >= 0)
    assert np.all(np.diff(hs.src_by_src) >= 0)


# --------------------------------------------------------------------- #
# PsiService: delta rebuilds + batched query layer
# --------------------------------------------------------------------- #
def _forbid_full_rebuilds(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("full operator rebuild on the delta path")
    monkeypatch.setattr(operators_mod, "build_operators", boom)
    monkeypatch.setattr(operators_mod.HostOperators, "from_graph",
                        classmethod(lambda cls, *a, **k: boom()))


def test_service_pallas_delta_update_roundtrip(platform, monkeypatch):
    """The acceptance path: PsiService(backend='pallas') absorbs an activity
    update through the O(Δ) patch (no full rebuild) and serves rank_of."""
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9, backend="pallas")
    u = int(svc.top_k(5)[0][-1])
    rank_before = int(svc.rank_of(np.asarray([u]))[0])
    _forbid_full_rebuilds(monkeypatch)
    svc.update_activity(np.asarray([u]), lam=np.asarray([5.0]))
    rank_after = int(svc.rank_of(np.asarray([u]))[0])
    assert rank_after <= rank_before          # posting more can't hurt
    lam2 = act.lam.copy()
    lam2[u] = 5.0
    psi_true, _ = exact_psi(g, Activity(lam2, act.mu))
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_service_add_edges_delta(platform, backend, monkeypatch):
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9, backend=backend)
    svc.scores()
    _forbid_full_rebuilds(monkeypatch)
    src = np.asarray([0, 1, 2], np.int32)
    dst = np.asarray([10, 11, 12], np.int32)
    svc.add_edges(src, dst)
    g2 = Graph(g.n, np.concatenate([g.src, src]),
               np.concatenate([g.dst, dst])).dedup()
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


@pytest.mark.parametrize("backend", ["pallas", "distributed"])
def test_service_remove_edges_fallback(platform, backend):
    """Backends without an incremental shrink hook serve removals through
    the filtered-graph re-prepare fallback — and stay exact."""
    g, act, _, _ = platform
    opts = dict(mesh=_mesh_1x1()) if backend == "distributed" else {}
    svc = PsiService(g, act, tol=1e-9, backend=backend, engine_opts=opts)
    svc.scores()
    # remove two real edges plus one absent tombstone (must be a no-op)
    rm_s = np.asarray([g.src[0], g.src[g.m // 2], g.src[1]], np.int32)
    rm_d = np.asarray([g.dst[0], g.dst[g.m // 2],
                       (g.dst[1] + 1) % g.n], np.int32)
    if rm_s[2] == rm_d[2]:                        # avoid accidental self-loop
        rm_d[2] = (rm_d[2] + 1) % g.n
    svc.remove_edges(rm_s, rm_d)
    keep = ~np.isin(g.src.astype(np.int64) * g.n + g.dst,
                    rm_s.astype(np.int64) * g.n + rm_d)
    g2 = Graph(g.n, g.src[keep], g.dst[keep])
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


@pytest.mark.parametrize("backend", ["pallas", "distributed"])
def test_service_interleaved_add_remove_parity(platform, backend):
    """add → remove → add through one service matches a from-scratch solve
    on the final graph (the removal rebuild must not lose earlier adds)."""
    g, act, _, _ = platform
    opts = dict(mesh=_mesh_1x1()) if backend == "distributed" else {}
    svc = PsiService(g, act, tol=1e-9, backend=backend, engine_opts=opts)
    svc.scores()
    add1_s = np.asarray([0, 1], np.int32)
    add1_d = np.asarray([20, 21], np.int32)
    svc.add_edges(add1_s, add1_d)
    svc.remove_edges(np.asarray([0, g.src[0]], np.int32),
                     np.asarray([20, g.dst[0]], np.int32))   # incl. new edge
    add2_s = np.asarray([2], np.int32)
    add2_d = np.asarray([22], np.int32)
    svc.add_edges(add2_s, add2_d)
    g1 = Graph(g.n, np.concatenate([g.src, add1_s]),
               np.concatenate([g.dst, add1_d])).dedup()
    rm = np.asarray([0 * g.n + 20, int(g.src[0]) * g.n + int(g.dst[0])])
    keep = ~np.isin(g1.src.astype(np.int64) * g1.n + g1.dst, rm)
    g2 = Graph(g.n, np.concatenate([g1.src[keep], add2_s]),
               np.concatenate([g1.dst[keep], add2_d])).dedup()
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


def test_service_distributed_backend_serves(platform):
    g, act, psi_true, _ = platform
    svc = PsiService(g, act, tol=1e-9, backend="distributed")
    top, vals = svc.top_k(3)
    assert np.all(np.diff(vals) <= 0)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


def test_ranking_cache_memoized_and_invalidated(platform, monkeypatch):
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9)
    users = np.asarray([1, 2, 3])
    svc.rank_of(users)
    cache = svc._cache
    assert cache is not None and cache._order is not None
    calls = {"n": 0}
    orig = np.argsort

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(np, "argsort", counting)
    svc.rank_of(users)                       # memoized: no new sort
    svc.top_k(4)                             # reuses the cached order too
    assert calls["n"] == 0
    assert svc._cache is cache
    svc.update_activity(np.asarray([1]), mu=np.asarray([0.9]))
    assert svc._cache is None                # mutation invalidates
    svc.rank_of(users)
    assert calls["n"] >= 1


def test_update_activity_broadcasts_scalar(platform):
    """Pre-refactor API: a scalar (or length-1) rate applies to all users."""
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9)
    users = np.asarray([1, 2, 3])
    svc.update_activity(users, lam=0.5)
    lam2 = act.lam.copy()
    lam2[users] = 0.5
    psi_true, _ = exact_psi(g, Activity(lam2, act.mu))
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6
    svc.update_activity(users, mu=np.asarray([0.25]))   # length-1 broadcast
    assert np.isfinite(svc.scores()).all()


def test_top_k_clips_to_n(platform):
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9)
    idx, vals = svc.top_k(g.n + 5)            # uncached path
    assert idx.shape == (g.n,)
    svc.rank_of(np.asarray([0]))              # populate the sorted order
    idx2, _ = svc.top_k(g.n + 5)              # cached path agrees
    assert idx2.shape == (g.n,)


def test_delta_update_does_not_retrace(platform):
    """Activity patches keep array shapes, so the compiled solver loop must
    be reused — the O(Δ) serving claim dies if every update recompiles."""
    g, act, _, _ = platform
    eng = make_engine("reference", graph=g, activity=act)
    eng.run(tol=1e-9)
    compiles = eng._loop._cache_size()
    eng.patch_activity(np.asarray([3]), lam=np.asarray([2.0]))
    eng.run(tol=1e-9)
    assert eng._loop._cache_size() == compiles


def test_service_warm_start_fewer_iterations(platform):
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9)
    cold = svc.last_iterations()
    svc.update_activity(np.asarray([7]), mu=np.asarray([act.mu[7] * 1.01]))
    assert svc.last_iterations() < cold


# --------------------------------------------------------------------- #
# Regime autotuning + acceleration: the auto / accelerated backends
# --------------------------------------------------------------------- #
def _graph_for(kind: str) -> Graph:
    if kind == "hyper_sparse":
        return powerlaw_configuration(600, 4000, seed=11)
    return clustered_blocks(512, 30_000, block=128, p_in=1.0, seed=12)


@pytest.mark.parametrize("act_kind", ["het", "hom"])
@pytest.mark.parametrize("graph_kind", ["hyper_sparse", "clustered"])
@pytest.mark.parametrize("backend", ["auto", "accelerated"])
def test_parity_across_regimes(backend, graph_kind, act_kind):
    """auto/accelerated agree with reference to ≤ 1e-6 on both activity
    regimes × both graph regimes (the clustered graph exercises the BSR
    kernel path, the hyper-sparse one the edge-tile path)."""
    g = _graph_for(graph_kind)
    act = (heterogeneous(g.n, seed=13) if act_kind == "het"
           else homogeneous(g.n))
    ref = make_engine("reference", graph=g, activity=act).run(tol=1e-9)
    eng = make_engine(backend, graph=g, activity=act)
    res = eng.run(tol=1e-9)
    assert np.abs(np.asarray(res.psi) - np.asarray(ref.psi)).max() <= 1e-6
    if backend == "auto":   # the planner must separate the two regimes
        assert eng.regime == ("edge_tile" if graph_kind == "hyper_sparse"
                              else "bsr")


def test_accelerated_backend_fewer_matvecs(platform):
    g, act, _, _ = platform
    ref = make_engine("reference", graph=g, activity=act).run(tol=1e-6)
    acc = make_engine("accelerated", graph=g, activity=act).run(tol=1e-6)
    assert bool(acc.converged)
    assert int(acc.matvecs) < int(ref.matvecs)


def test_pallas_accelerate_opt_in(platform):
    g, act, psi_true, _ = platform
    eng = make_engine("pallas", graph=g, activity=act, accelerate=True)
    res = eng.run(tol=1e-6)
    assert bool(res.converged)
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6


def test_check_every_cadence(platform):
    """iterations land on a multiple of k, overshoot < k, same answer."""
    g, act, psi_true, _ = platform
    base = make_engine("reference", graph=g, activity=act).run(tol=1e-9)
    eng = make_engine("reference", graph=g, activity=act, check_every=4)
    res = eng.run(tol=1e-9)
    assert int(res.iterations) % 4 == 0
    assert int(base.iterations) <= int(res.iterations) \
        < int(base.iterations) + 4
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6
    pal = make_engine("pallas", graph=g, activity=act, check_every=3)
    resp = pal.run(tol=1e-9)
    assert int(resp.iterations) % 3 == 0
    assert np.abs(np.asarray(resp.psi) - psi_true).max() <= 1e-6


def test_autotuner_plan_cache_no_replan_on_patch_activity(platform):
    """The regression the serving path depends on: an activity patch (and a
    warm re-prepare over the same graph) must reuse the cached plan and the
    already-compiled solver loop."""
    from repro.kernels.autotune import PlanCache
    g, act, _, _ = platform
    cache = PlanCache()
    eng = make_engine("auto", graph=g, activity=act, plan_cache=cache)
    assert (cache.hits, cache.misses) == (0, 1)
    eng.run(tol=1e-6)
    loop = eng._loop
    compiles = loop._cache_size()
    eng.patch_activity(np.asarray([3]), lam=np.asarray([2.0]))
    eng.run(tol=1e-6)
    assert cache.misses == 1               # no re-plan on the delta path
    assert eng._loop is loop and loop._cache_size() == compiles
    eng.prepare(g, act)                    # full rebuild, same structure
    eng.run(tol=1e-6)
    assert (cache.hits, cache.misses) == (1, 1)
    assert eng._loop is loop and loop._cache_size() == compiles


def test_service_auto_backend_delta_roundtrip(platform, monkeypatch):
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9, backend="auto")
    svc.scores()
    _forbid_full_rebuilds(monkeypatch)
    u = 5
    svc.update_activity(np.asarray([u]), lam=np.asarray([4.0]))
    lam2 = act.lam.copy()
    lam2[u] = 4.0
    psi_true, _ = exact_psi(g, Activity(lam2, act.mu))
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


def test_bsr_regime_delta_updates(monkeypatch):
    """BSR-regime pallas absorbs activity and edge patches in place."""
    g = _graph_for("clustered")
    act = heterogeneous(g.n, seed=13)
    svc = PsiService(g, act, tol=1e-9, backend="pallas",
                     engine_opts=dict(regime="bsr"))
    svc.scores()
    _forbid_full_rebuilds(monkeypatch)
    svc.update_activity(np.asarray([2]), mu=np.asarray([0.8]))
    # in-block edge insert (block (0,0) exists) and a cross-block edge
    # that forces the internal format rebuild — both stay correct
    src = np.asarray([0, 3], np.int32)
    dst = np.asarray([7, 400], np.int32)
    svc.add_edges(src, dst)
    g2 = Graph(g.n, np.concatenate([g.src, src]),
               np.concatenate([g.dst, dst])).dedup()
    act2 = Activity(act.lam, np.where(np.arange(g.n) == 2, 0.8, act.mu))
    psi_true, _ = exact_psi(g2, act2)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


def test_edge_tile_patch_overflow_rebuilds(platform, monkeypatch):
    """Overflowing a node tile's sentinel slots triggers the edge-tile
    format rebuild (never a full operator rebuild) and stays exact."""
    g, act, _, _ = platform
    svc = PsiService(g, act, tol=1e-9, backend="pallas")
    svc.scores()
    eng = svc.engine
    blocks_before = eng.fmt_host.num_blocks
    _forbid_full_rebuilds(monkeypatch)
    # enough new edges into tile 0 (dst < 256) to exhaust its free slots
    need = int((eng._tile_capacity - eng._tile_used)[0]) + 16
    existing = set(zip(g.src.tolist(), g.dst.tolist()))
    rng = np.random.default_rng(0)
    pairs = set()
    while len(pairs) < need:
        s = int(rng.integers(0, g.n))
        d = int(rng.integers(0, min(eng.tile, g.n)))
        if s != d and (s, d) not in existing:
            pairs.add((s, d))
    pairs = sorted(pairs)
    src = np.asarray([p[0] for p in pairs], np.int32)
    dst = np.asarray([p[1] for p in pairs], np.int32)
    svc.add_edges(src, dst)
    assert eng.fmt_host.num_blocks > blocks_before
    g2 = Graph(g.n, np.concatenate([g.src, src]),
               np.concatenate([g.dst, dst])).dedup()
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


# --------------------------------------------------------------------- #
# Distributed delta hook + chunk-level acceleration
# --------------------------------------------------------------------- #
def _mesh_1x1():
    """Pin a 1×1 mesh: partition shapes must not depend on how many host
    devices an earlier test (launch/dryrun) forced into the process."""
    import jax
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def test_distributed_patch_edges_block_local(platform, monkeypatch):
    """The delta hook never re-partitions: new edges are merged into their
    node-stable blocks and only the touched device rows are rewritten."""
    import repro.core.distributed as dist_mod
    g, act, _, _ = platform
    eng = make_engine("distributed", graph=g, activity=act,
                      mesh=_mesh_1x1())
    prev = eng.run(tol=1e-9)

    def boom(*a, **k):
        raise AssertionError("re-partition on the delta path")

    monkeypatch.setattr(dist_mod, "partition_2d", boom)
    _forbid_full_rebuilds(monkeypatch)
    src = np.asarray([0, 1, 2, 0], np.int32)
    dst = np.asarray([10, 11, 12, 10], np.int32)   # dup collapses
    assert eng.patch_edges(src, dst) is True
    res = eng.run(tol=1e-9, s0=prev.s)
    g2 = Graph(g.n, np.concatenate([g.src, src]),
               np.concatenate([g.dst, dst])).dedup()
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6


def test_distributed_patch_edges_overflow_regrows_with_warning():
    """A full block (e_max exhausted) is a genuine overflow: the default
    hook regrows the partition in place — warning with the overflowing
    block and required capacity, never a silent no-op — and stays exact."""
    g = erdos_renyi(100, 256, seed=6)              # e_max == m: zero slack
    act = heterogeneous(g.n, seed=7)
    eng = make_engine("distributed", graph=g, activity=act,
                      mesh=_mesh_1x1())
    prev = eng.run(tol=1e-9)
    assert int(eng.dist.part.e_max) == g.m
    with pytest.warns(RuntimeWarning,
                      match=r"block \(row=0, col=0\).*e_max=256.*>= 257"):
        assert eng.patch_edges(np.asarray([0]), np.asarray([50])) is True
    assert int(eng.dist.part.e_max) > g.m          # capacity actually grew
    res = eng.run(tol=1e-9, s0=prev.s)
    g2 = Graph(g.n, np.concatenate([g.src, [0]]),
               np.concatenate([g.dst, [50]])).dedup()
    psi_true, _ = exact_psi(g2, act)
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6
    # service path rides the regrow transparently
    svc = PsiService(g, act, tol=1e-9, backend="distributed",
                     engine_opts=dict(mesh=_mesh_1x1()))
    svc.scores()
    with pytest.warns(RuntimeWarning):
        svc.add_edges(np.asarray([0]), np.asarray([50]))
    assert np.abs(svc.scores() - psi_true).max() <= 1e-6


def test_distributed_patch_edges_overflow_raise_mode():
    """on_overflow='raise' names the overflowing block and the capacity the
    insert needs (for callers that budget e_max themselves)."""
    from repro.core.distributed import BlockOverflowError
    g = erdos_renyi(100, 256, seed=6)
    act = heterogeneous(g.n, seed=7)
    eng = make_engine("distributed", graph=g, activity=act,
                      mesh=_mesh_1x1(), on_overflow="raise")
    eng.run(tol=1e-9)
    with pytest.raises(BlockOverflowError,
                       match=r"\(row=0, col=0\).*capacity >= 257") as ei:
        eng.patch_edges(np.asarray([0]), np.asarray([50]))
    assert ei.value.block == (0, 0)
    assert ei.value.e_max == 256 and ei.value.required == 257
    # the probe mutated nothing: the host mirror still matches the
    # unpatched graph, so a caught raise leaves the engine consistent
    assert eng.graph.m == g.m
    res = eng.run(tol=1e-9)
    psi_unpatched, _ = exact_psi(g, act)
    assert np.abs(np.asarray(res.psi) - psi_unpatched).max() <= 1e-6
    with pytest.raises(ValueError, match="on_overflow"):
        make_engine("distributed", on_overflow="explode")


def test_distributed_dispatch_finalize_compose(platform):
    """make_dispatch ∘ make_finalize reproduces the fused make_step — the
    explicit PartialReduction boundary the overlapped executors build on."""
    import jax
    from repro.core.distributed import DistributedPsi
    g, act, _, _ = platform
    dist = DistributedPsi.from_graph(g, act, _mesh_1x1())
    step = jax.jit(dist.make_step())
    dispatch = jax.jit(dist.make_dispatch())
    finalize = jax.jit(dist.make_finalize())
    s = dist.arrays.c_src
    for _ in range(3):
        s_fused, gap_fused = step(s, dist.arrays)
        handle = dispatch(s, dist.arrays)
        s_split, gap_split = finalize(handle, dist.arrays)
        np.testing.assert_allclose(np.asarray(s_split),
                                   np.asarray(s_fused), rtol=1e-7, atol=0)
        assert float(gap_split) == pytest.approx(float(gap_fused),
                                                 rel=1e-6)
        s = s_fused


def test_distributed_chunk_accelerate(platform):
    g, act, psi_true, _ = platform
    eng = make_engine("distributed", graph=g, activity=act,
                      accelerate=True, chunk_iters=4, mesh=_mesh_1x1())
    res = eng.run(tol=1e-9)
    assert bool(res.converged)
    assert np.abs(np.asarray(res.psi) - psi_true).max() <= 1e-6


def test_psi_driver_accelerate_inherited(platform):
    from repro.runtime import PsiDriver
    g, act, psi_true, _ = platform
    eng = make_engine("distributed", graph=g, activity=act,
                      accelerate=True, chunk_iters=4, mesh=_mesh_1x1())
    drv = PsiDriver.from_engine(eng)
    assert drv.accelerate is True
    rep = drv.run(tol=1e-11)     # driver gap is unscaled (no ‖B‖ factor)
    assert np.abs(rep.psi - psi_true).max() <= 1e-6
