"""Decision observability (PR 10): DecisionRecord/DecisionLog semantics,
plan_regime's audit trail (candidate table, density-gate prunes, cache
hit/miss, the ``source`` provenance field), the self-calibrating cost
model (median/MAD factors, skew → mis-rank → recovery), and the
EXPLAIN-ANALYZE renderers up through ``PsiService.explain()`` — plus the
bitwise-ψ parity contract with explain + calibration armed."""
import json
import os
import tempfile

import numpy as np
import pytest

from repro import obs
from repro.core import (Activity, PsiService, RATE_FLOOR, heterogeneous,
                        make_engine)
from repro.graphs import clustered_blocks, powerlaw_configuration
from repro.kernels import autotune
from repro.obs import calibrate as obs_calibrate
from repro.obs import explain as obs_explain
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs.explain import (Candidate, DecisionLog, DecisionRecord,
                               Pruned, decisions_for, explain_tree,
                               format_cost, render_decision)
from repro.obs.metrics import MetricsRegistry

# skewed (edge, bsr, node) bytes/slot: edge_tile looks ~free, BSR looks
# ruinous — the calibration acceptance drill injects these
SKEW = (0.001, 1e5, 16.0)


@pytest.fixture
def fresh_obs():
    """Isolated registry/tracker/decision-log per test."""
    prev = obs.configure(registry=MetricsRegistry(),
                         tracer=obs.Tracer(None),
                         tracker=obs.ConvergenceTracker(),
                         decisions=DecisionLog())
    obs_log.clear()
    yield obs_metrics.get_registry()
    obs.restore(prev)


@pytest.fixture(scope="module")
def sparse_graph():
    return powerlaw_configuration(1_000, 7_000, seed=17)


@pytest.fixture(scope="module")
def blocky_graph():
    return clustered_blocks(256, 12_000, block=128, p_in=1.0, seed=3)


def _fake_bench_bsr_wins(graph, plan, dtype, interpret):
    return 100.0 if plan.regime == "bsr" else 5_000.0


# --------------------------------------------------------------------- #
# DecisionLog / DecisionRecord semantics
# --------------------------------------------------------------------- #
def test_decision_log_ring_and_filters(fresh_obs):
    log = obs_explain.get_log()
    for i in range(5):
        obs_explain.record_decision("regime_plan", f"site{i}")
    obs_explain.record_decision("solver_choice", "s")
    assert len(log) == 6
    assert [r.site for r in log.recent(2, kind="regime_plan")] \
        == ["site3", "site4"]
    assert log.last(kind="solver_choice").kind == "solver_choice"
    assert log.last(kind="early_stop") is None


def test_decision_log_bounded():
    log = DecisionLog(keep=4)
    for i in range(10):
        log.record(DecisionRecord("regime_plan", f"s{i}"))
    assert len(log) == 4
    assert log.recent()[0].site == "s6"


def test_record_decision_counts_by_kind(fresh_obs):
    obs_explain.record_decision("regime_plan", "a")
    obs_explain.record_decision("regime_plan", "b")
    obs_explain.record_decision("early_stop", "c")
    assert fresh_obs.value("psi_plan_decisions_total",
                           kind="regime_plan") == 2
    assert fresh_obs.value("psi_plan_decisions_total",
                           kind="early_stop") == 1


def test_disable_nulls_the_decision_log(sparse_graph):
    prev = obs.disable()
    try:
        assert obs_explain.record_decision("regime_plan", "x") is None
        autotune.plan_regime(sparse_graph, cache=None, calibration=None)
        assert len(obs_explain.get_log()) == 0
    finally:
        obs.restore(prev)
    assert obs_explain.get_log().enabled


def test_decisions_for_prefers_matching_shape(fresh_obs):
    obs_explain.record_decision("regime_plan", "a", inputs=dict(n=10, m=20))
    obs_explain.record_decision("regime_plan", "b", inputs=dict(n=99, m=77))
    picked = decisions_for(n=10, m=20)
    assert [r.site for r in picked] == ["a"]
    # no match for the shape → newest of the kind still surfaces
    picked = decisions_for(n=1, m=2)
    assert [r.site for r in picked] == ["b"]


def test_decision_record_json_roundtrips():
    rec = DecisionRecord(
        "regime_plan", "plan_regime", inputs=dict(n=5, m=9),
        cache="miss", chosen="edge_tile(tile=256)", source="model",
        candidates=[Candidate("edge_tile(tile=256)", est=1024.0,
                              chosen=True)],
        pruned=[Pruned("bsr(ts=128,td=128)", "BSR_MIN_OCCUPANCY",
                       detail=dict(occupancy=0.001))])
    doc = json.loads(json.dumps(rec.to_json()))
    assert doc["kind"] == "regime_plan" and doc["cache"] == "miss"
    assert doc["candidates"][0]["chosen"] is True
    assert doc["pruned"][0]["reason"] == "BSR_MIN_OCCUPANCY"


# --------------------------------------------------------------------- #
# plan_regime's audit trail
# --------------------------------------------------------------------- #
def test_plan_regime_records_candidates_prunes_and_cache(fresh_obs,
                                                         sparse_graph):
    cache = autotune.PlanCache()
    plan = autotune.plan_regime(sparse_graph, cache=cache, calibration=None)
    rec = obs_explain.get_log().last(kind="regime_plan")
    assert rec.cache == "miss" and rec.chosen == plan.label()
    assert rec.source == "model" and plan.source == "model"
    assert sum(c.chosen for c in rec.candidates) == 1
    assert len(rec.candidates) >= 2          # alternatives kept, not just winner
    # hyper-sparse graph: every BSR parameterization is pruned before its
    # occupancy pass, since a block per destination tile already costs
    # more than the chosen edge-tile plan
    assert rec.pruned and all(p.reason == "BSR_BYTE_FLOOR"
                              for p in rec.pruned)
    assert all(p.detail["est_at_least"] > plan.est_bytes
               for p in rec.pruned)

    autotune.plan_regime(sparse_graph, cache=cache, calibration=None)
    rec2 = obs_explain.get_log().last(kind="regime_plan")
    assert rec2.cache == "hit" and rec2.chosen == plan.label()
    assert fresh_obs.value("psi_plan_cache_hits_total") == 1
    assert fresh_obs.value("psi_plan_cache_misses_total") == 1


def test_plan_cache_size_gauge_tracks_global_cache_only(fresh_obs,
                                                        sparse_graph):
    before = len(autotune.PLAN_CACHE)
    autotune.plan_regime(sparse_graph, calibration=None)
    assert fresh_obs.value("psi_plan_cache_size") == before + 1
    # a private cache must not fight the process-level gauge
    autotune.plan_regime(sparse_graph, cache=autotune.PlanCache(),
                         calibration=None)
    assert fresh_obs.value("psi_plan_cache_size") == before + 1


def test_timed_planning_records_the_density_gate(fresh_obs, sparse_graph,
                                                 monkeypatch):
    """Timed plans skip the byte floor, so the density gate prunes the
    hyper-sparse graph's BSR candidates, each with its occupancy."""
    monkeypatch.setattr(autotune, "_microbench_step", _fake_bench_bsr_wins)
    autotune.plan_regime(sparse_graph, cache=None, microbench=True,
                         calibration=None)
    rec = obs_explain.get_log().last(kind="regime_plan")
    assert rec.pruned and all(p.reason == "BSR_MIN_OCCUPANCY"
                              for p in rec.pruned)
    assert all(p.detail["occupancy"] < autotune.BSR_MIN_OCCUPANCY
               for p in rec.pruned)


def test_microbench_sets_source_and_feeds_store(fresh_obs, blocky_graph,
                                                monkeypatch):
    monkeypatch.setattr(autotune, "_microbench_step", _fake_bench_bsr_wins)
    store = obs_calibrate.CalibrationStore(env="test|cpu|False")
    plan = autotune.plan_regime(blocky_graph, cache=None, microbench=True,
                                calibration=store)
    assert plan.source == "microbench" and plan.regime == "bsr"
    rec = obs_explain.get_log().last(kind="regime_plan")
    assert rec.source == "microbench"
    assert all(c.measured_us > 0 for c in rec.candidates)
    # one observation per surviving candidate landed in the store
    assert len(store) == len(rec.candidates)
    assert set(store.factors()) == {"bsr", "edge_tile"}


# --------------------------------------------------------------------- #
# CalibrationStore math
# --------------------------------------------------------------------- #
def test_store_ratio_median_mad_and_confidence():
    store = obs_calibrate.CalibrationStore(env="e")
    assert store.observe("edge_tile", 0.0, 5.0) is None   # no information
    assert store.observe("edge_tile", 10.0, -1.0) is None
    assert store.factor("edge_tile") is None
    assert store.observe("edge_tile", 100.0, 200.0) == 2.0
    assert store.factor("edge_tile") is None              # below min_samples
    store.observe("edge_tile", 100.0, 400.0)
    f = store.factor("edge_tile")
    assert f == {"median": 3.0, "mad": 1.0, "count": 2}
    assert store.corrected_us("edge_tile", 10.0) == 30.0
    assert store.corrected_us("bsr", 10.0) is None


def test_store_multipliers_cannot_flip_unknown_regimes():
    store = obs_calibrate.CalibrationStore(env="e")
    assert store.multipliers({"edge_tile", "bsr"}) == {}
    store.observe("edge_tile", 1.0, 4.0)
    store.observe("edge_tile", 1.0, 4.0)
    mult = store.multipliers({"edge_tile", "bsr"})
    # the unknown regime inherits the confident median: uniform scaling,
    # identical relative ordering
    assert mult == {"edge_tile": 4.0, "bsr": 4.0}


def test_store_generation_bumps_only_on_material_drift():
    store = obs_calibrate.CalibrationStore(env="e")
    store.observe("bsr", 1.0, 2.0)
    assert store.generation == 0                  # not yet confident
    store.observe("bsr", 1.0, 2.0)
    assert store.generation == 1                  # first publication
    store.observe("bsr", 1.0, 2.01)               # median moves <10%
    assert store.generation == 1
    gen = store.generation
    for _ in range(8):
        store.observe("bsr", 1.0, 10.0)           # median drifts hard
    assert store.generation > gen                 # material drift republishes


def test_store_save_load_roundtrip(tmp_path):
    store = obs_calibrate.CalibrationStore(env="e")
    store.observe("bsr", 2.0, 6.0)
    store.observe("bsr", 2.0, 10.0)
    path = os.path.join(tmp_path, "CALIB_power_psi.json")
    snap = store.save(path)
    assert snap["entries"][0]["regime"] == "bsr"
    fresh = obs_calibrate.CalibrationStore(env="e")
    assert fresh.load(path) == 1
    assert fresh.factor("bsr") == store.factor("bsr")
    assert fresh.load(os.path.join(tmp_path, "missing.json")) == 0


def test_store_is_per_environment():
    store = obs_calibrate.CalibrationStore(env="cpu|cpu|False")
    store.observe("bsr", 1.0, 3.0, env="tpu|v5e|True")
    store.observe("bsr", 1.0, 3.0, env="tpu|v5e|True")
    assert store.factor("bsr") is None            # wrong machine class
    assert store.factor("bsr", env="tpu|v5e|True")["median"] == 3.0


# --------------------------------------------------------------------- #
# the acceptance drill: skew → mis-rank → calibrate → recover
# --------------------------------------------------------------------- #
def test_skewed_model_misranks_then_calibration_recovers(fresh_obs,
                                                         blocky_graph,
                                                         monkeypatch):
    monkeypatch.setattr(autotune, "_microbench_step", _fake_bench_bsr_wins)
    uncal = autotune.plan_regime(blocky_graph, cache=None, calibration=None,
                                 slot_bytes=SKEW)
    assert uncal.regime == "edge_tile"            # the skew mis-ranks

    store = obs_calibrate.CalibrationStore(env="test|cpu|False")
    bench = autotune.plan_regime(blocky_graph, cache=None, microbench=True,
                                 calibration=store, slot_bytes=SKEW)
    assert bench.regime == "bsr"                  # measured ground truth
    events = obs_log.recent(name="model_misranked")
    assert events and events[-1]["basis"] == "microbench"

    recovered = autotune.plan_regime(blocky_graph, cache=None,
                                     calibration=store, slot_bytes=SKEW)
    assert recovered.regime == "bsr"
    assert recovered.source == "calibrated"
    rec = obs_explain.get_log().last(kind="regime_plan")
    assert rec.source == "calibrated"
    assert rec.calibration and rec.calibration["factors"]
    chosen = next(c for c in rec.candidates if c.chosen)
    assert chosen.calibrated_us is not None
    assert fresh_obs.value("psi_plan_misprediction_ratio") > 1.0
    assert obs_log.recent(name="model_misranked")[-1]["basis"] \
        == "calibration"


def test_calibration_generation_invalidates_plan_cache(fresh_obs,
                                                       blocky_graph,
                                                       monkeypatch):
    monkeypatch.setattr(autotune, "_microbench_step", _fake_bench_bsr_wins)
    store = obs_calibrate.CalibrationStore(env="test|cpu|False")
    cache = autotune.PlanCache()
    p1 = autotune.plan_regime(blocky_graph, cache=cache, calibration=store,
                              slot_bytes=SKEW)
    p1b = autotune.plan_regime(blocky_graph, cache=cache, calibration=store,
                               slot_bytes=SKEW)
    assert p1b == p1 and len(cache) == 1
    # material recalibration bumps the generation → the stale memoized
    # plan is not served again
    autotune.plan_regime(blocky_graph, cache=None, microbench=True,
                         calibration=store, slot_bytes=SKEW)
    assert store.generation >= 1
    p2 = autotune.plan_regime(blocky_graph, cache=cache, calibration=store,
                              slot_bytes=SKEW)
    assert p2.regime == "bsr" and p2.source == "calibrated"
    assert len(cache) == 2


# --------------------------------------------------------------------- #
# renderers
# --------------------------------------------------------------------- #
def test_format_cost_units():
    assert format_cost(None, "bytes") == "-"
    assert format_cost(512.0, "bytes") == "512B"
    assert format_cost(200 * 1024.0, "bytes") == "200.00KB"
    assert format_cost(3 << 20, "bytes") == "3.00MB"
    assert format_cost(250.0, "us") == "250.0µs"
    assert format_cost(12_500.0, "us") == "12.50ms"
    assert format_cost(5.8e5, "edges") == "5.8e+05 edges"


def test_render_decision_marks_winner_and_regret():
    rec = DecisionRecord(
        "regime_plan", "plan_regime", inputs=dict(n=10, m=20),
        cache="miss", chosen="a", source="model",
        candidates=[Candidate("b", est=150.0), Candidate("a", est=100.0,
                                                         chosen=True)])
    lines = render_decision(rec)
    assert lines[0].startswith(
        "regime_plan via plan_regime [PLAN_CACHE miss] source=model")
    assert lines[1].lstrip().startswith("chosen  a")   # winner sorts first
    assert "(+50%)" in lines[2]                        # regret vs winner


def test_explain_tree_renders_empty_and_full(fresh_obs):
    empty = explain_tree(header="H")
    assert empty.splitlines()[0] == "H"
    assert "no recorded decisions" in empty
    rec = obs_explain.record_decision(
        "solver_choice", "choose_solver", inputs=dict(n=4),
        chosen="push", candidates=[Candidate("push", est=1.0, unit="edges",
                                             chosen=True)])
    out = explain_tree(header="H", decisions=[rec],
                       query=dict(op="scores", cache="hit", stale=False,
                                  seconds=1e-3),
                       extra=dict(k="v"))
    assert "├─ solver_choice via choose_solver" in out
    assert "query op=scores cache=hit stale=False wall=1.00ms" in out
    assert out.splitlines()[-1] == "└─ k=v"


# --------------------------------------------------------------------- #
# service-level explain + parity
# --------------------------------------------------------------------- #
def _small_service(backend="reference"):
    import jax.numpy as jnp
    g = powerlaw_configuration(300, 1_800, seed=5)
    act = heterogeneous(g.n, seed=6)
    return g, PsiService(g, act, tol=1e-8, backend=backend,
                         dtype=jnp.float64)


def test_service_explain_renders_resolve_and_solver_choice(fresh_obs):
    g, svc = _small_service()
    svc.update_activity(np.asarray([1]), lam=np.asarray([3.0]))
    svc.top_k(3)
    tree = svc.explain()
    assert tree.splitlines()[0].startswith(
        "EXPLAIN ANALYZE — power-ψ [backend=reference]")
    assert "resolve #" in tree and "solver_choice via choose_solver" in tree
    assert "query op=" in tree
    # the solver decision carries the measured dirty fraction
    rec = obs_explain.get_log().last(kind="solver_choice")
    assert 0.0 < rec.inputs["dirty_frac"] <= 1.0
    assert rec.inputs["n"] == g.n


def test_push_backend_records_early_stop_decision(fresh_obs):
    g = powerlaw_configuration(400, 2_400, seed=11)
    act = heterogeneous(g.n, seed=12)
    eng = make_engine("push", graph=g, activity=act)
    res, cert = eng.run_top_k(5, tol=1e-10)
    rec = obs_explain.get_log().last(kind="early_stop")
    assert rec is not None and rec.site == "PushEngine.run_top_k"
    assert rec.inputs["k"] == 5
    want = "certified_early_stop" if cert.certified else "exhausted_to_tol"
    assert rec.chosen == want
    assert {c.name for c in rec.candidates} \
        == {"certified_early_stop", "exhausted_to_tol"}


def test_fleet_records_bucket_regime_rule(fresh_obs):
    from repro.serving import BucketPolicy, TenantFleet
    fleet = TenantFleet(backend="reference", tol=1e-8,
                        policy=BucketPolicy((64,), edge_quantum=256))
    g = powerlaw_configuration(48, 200, seed=2)
    fleet.admit("a", g, heterogeneous(g.n, seed=3))
    fleet.solve()
    rec = obs_explain.get_log().last(kind="bucket_regime")
    assert rec is not None and rec.chosen == "reference"
    assert "pinned" in next(c for c in rec.candidates if c.chosen) \
        .detail["rule"]


def test_bitwise_parity_with_explain_and_calibration_armed(fresh_obs):
    _, svc = _small_service()
    svc.update_activity(np.asarray([0]), lam=np.asarray([2.0]))
    psi_live = np.array(svc.scores(), copy=True)
    assert len(obs_explain.get_log()) > 0         # explain really armed

    # populated calibration store stays armed across obs.disable(): it is
    # planner input, not telemetry
    store = obs_calibrate.CalibrationStore(env="test|cpu|False")
    store.observe("edge_tile", 1.0, 7.0)
    store.observe("edge_tile", 1.0, 7.0)
    prev_store = obs_calibrate.set_store(store)
    prev = obs.disable()
    try:
        _, svc2 = _small_service()
        svc2.update_activity(np.asarray([0]), lam=np.asarray([2.0]))
        psi_null = np.array(svc2.scores(), copy=True)
        assert len(obs_explain.get_log()) == 0
    finally:
        obs.restore(prev)
        obs_calibrate.set_store(prev_store)
    assert np.array_equal(psi_live, psi_null)


def test_auto_engine_feeds_step_span_calibration(fresh_obs):
    import jax.numpy as jnp
    g = powerlaw_configuration(400, 2_400, seed=9)
    act = heterogeneous(g.n, seed=10)
    store = obs_calibrate.CalibrationStore(env="test|cpu|False",
                                           min_samples=1)
    prev_store = obs_calibrate.set_store(store)
    try:
        eng = make_engine("auto", graph=g, activity=act, dtype=jnp.float64)
        res = eng.run(tol=1e-10)
        assert res.converged
        assert len(store) == 1                    # one wall/iter sample
        (key,) = store._samples
        assert key[1] == eng.plan.regime
    finally:
        obs_calibrate.set_store(prev_store)


def test_obs_dump_carries_decisions_and_calibration(fresh_obs, tmp_path):
    obs_explain.record_decision("regime_plan", "x", chosen="a")
    snap = obs.dump(os.path.join(tmp_path, "dump.json"))
    assert snap["decisions"][-1]["site"] == "x"
    assert "calibration" in snap
