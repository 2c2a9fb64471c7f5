"""Units for the obs analysis-and-control layer (PR 9): SLO engine +
burn-rate alerting, span-stream profiler, and convergence watch +
pre-emptive supervision."""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs.convergence import ResolveRecord
from repro.obs.metrics import MetricsRegistry
from repro.obs.profile import Profile
from repro.obs.slo import (BurnRule, SLO, SLOEngine, counter_ratio,
                           default_slos, gauge_value, histogram_quantile)
from repro.obs.watch import ConvergenceWatch


@pytest.fixture
def fresh_obs():
    """Isolated sinks (registry + tracker + in-memory tracer) per test."""
    prev = obs.configure(registry=MetricsRegistry(),
                         tracer=obs.Tracer(None),
                         tracker=obs.ConvergenceTracker())
    obs_log.clear()
    yield obs_metrics.get_registry()
    obs.restore(prev)


# --------------------------------------------------------------------- #
# SLO engine
# --------------------------------------------------------------------- #
def test_signal_helpers_read_live_registry(fresh_obs):
    reg = fresh_obs
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.01, 0.02, 0.03):
        h.observe(v)
    reg.gauge("lag_s", "lag", ("lane",)).labels(lane="a").set(4.0)
    reg.counter("bad_total", "bad").inc(1)
    reg.counter("all_total", "all").inc(4)
    assert histogram_quantile("lat_seconds", 1.0)() == pytest.approx(0.03)
    assert gauge_value("lag_s", lane="a")() == 4.0
    assert counter_ratio("bad_total", "all_total")() == pytest.approx(0.25)
    # absent series: None, never an exception
    assert histogram_quantile("nope_seconds", 0.99)() is None
    assert gauge_value("nope")() is None
    assert counter_ratio("bad_total", "nope_total")() is None


def test_slo_no_data_is_compliant_and_counted_as_good(fresh_obs):
    t = [0.0]
    eng = SLOEngine([SLO("s", lambda: None, target=1.0)],
                    clock=lambda: t[0])
    eng.tick()
    row = eng.report()["slos"][0]
    assert row["samples"] == 1 and row["bad_samples"] == 0
    assert row["meeting_target"] and row["budget_remaining"] == 1.0


def test_slo_violations_drain_the_error_budget(fresh_obs):
    t = [0.0]
    eng = SLOEngine([SLO("lat", lambda: 2.0, target=1.0,
                         objective=0.99)], clock=lambda: t[0])
    for _ in range(3):
        eng.tick()
        t[0] += 1.0
    rep = eng.report()
    row = rep["slos"][0]
    assert row["bad_samples"] == 3 and not row["meeting_target"]
    assert row["budget_remaining"] == 0.0 and not rep["ok"]
    fam = fresh_obs.get("psi_slo_violations_total")
    assert sum(ch.value for _, ch in fam.children()) == 3


def test_higher_is_better_objective_direction(fresh_obs):
    eng = SLOEngine([SLO("throughput", lambda: 80.0, target=100.0,
                         op=">=")], clock=lambda: 0.0)
    eng.tick()
    assert not eng.report()["slos"][0]["meeting_target"]


def test_burn_alert_needs_both_windows_and_fires_once(fresh_obs):
    t = [0.0]
    val = [0.0]
    slo = SLO("s", lambda: val[0], target=1.0, objective=0.9,
              rules=((10.0, 100.0, 2.0),))
    eng = SLOEngine([slo], clock=lambda: t[0])
    # long healthy history fills the slow window with good samples
    for _ in range(100):
        eng.tick()
        t[0] += 1.0
    # outage: fast window saturates quickly, slow window lags
    val[0] = 5.0
    fired_at = None
    for i in range(60):
        eng.tick()
        if fired_at is None and eng.report()["alerts_total"]:
            fired_at = i
        t[0] += 1.0
    rep = eng.report()
    assert fired_at is not None, "sustained outage must alert"
    # burn>2 with budget 0.1 needs bad_frac>0.2 in BOTH windows: the
    # 100-sample slow window requires >20 bad samples, so the alert must
    # arrive later than the fast window alone would allow
    assert fired_at >= 20
    # rising-edge dedupe: one alert despite ~40 more firing ticks
    assert rep["alerts_total"] == 1
    events = [e for e in obs_log.recent(500)
              if e["name"] == "slo_burn_alert"]
    assert len(events) == 1
    assert events[0]["slo"] == "s" and events[0]["burn_fast"] > 2.0


def test_burn_alert_rearms_after_recovery(fresh_obs):
    t = [0.0]
    val = [0.0]
    slo = SLO("s", lambda: val[0], target=1.0, objective=0.5,
              rules=((4.0, 8.0, 1.5),))
    eng = SLOEngine([slo], clock=lambda: t[0])

    def run(n, v):
        val[0] = v
        for _ in range(n):
            eng.tick()
            t[0] += 1.0

    run(10, 0.0)          # healthy baseline
    run(10, 9.0)          # first outage -> alert
    assert eng.report()["alerts_total"] == 1
    run(12, 0.0)          # recovery clears the fast window -> re-arm
    run(10, 9.0)          # second outage -> second alert
    assert eng.report()["alerts_total"] == 2


def test_broken_signal_is_an_error_event_not_an_outage(fresh_obs):
    def boom():
        raise RuntimeError("sensor detached")
    eng = SLOEngine([SLO("s", boom, target=1.0)], clock=lambda: 0.0)
    eng.tick()
    row = eng.report()["slos"][0]
    assert row["samples"] == 0 and row["meeting_target"]
    assert any(e["name"] == "slo_signal_error"
               for e in obs_log.recent(50))


def test_burn_rule_scaling_and_default_catalog(fresh_obs):
    r = BurnRule(300.0, 3600.0, 14.4).scaled(1.0 / 200.0)
    assert r.fast_s == pytest.approx(1.5)
    assert r.slow_s == pytest.approx(18.0)
    assert r.burn == 14.4
    names = {s.name for s in default_slos()}
    assert names == {"query_p99_latency", "freshness_staleness",
                     "certified_psi_error", "degraded_answer_ratio"}


def test_healthz_and_slo_http_endpoints(fresh_obs):
    eng = SLOEngine([SLO("s", lambda: 0.5, target=1.0)],
                    clock=lambda: 0.0)
    eng.tick()
    server = obs.start_http_server(0)     # ephemeral port
    try:
        port = server.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, json.load(r)
        status, hz = get("/healthz")
        assert status == 200 and hz["status"] == "ok"
        assert hz["metrics_enabled"] and not hz["slo_installed"]
        # no engine installed yet -> /slo is a 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            get("/slo")
        assert ei.value.code == 404
        eng.install()
        try:
            status, doc = get("/slo")
            assert status == 200
            assert doc["slos"][0]["name"] == "s" and doc["ok"]
            assert get("/healthz")[1]["slo_installed"]
        finally:
            eng.uninstall()
    finally:
        server.shutdown()


# --------------------------------------------------------------------- #
# span-stream profiler
# --------------------------------------------------------------------- #
def _span(name, ts, dur, *, id=None, parent=None, thread=0, **attrs):
    rec = dict(name=name, id=id or f"{name}@{ts}", parent=parent,
               depth=0 if parent is None else 1, thread=thread,
               ts=ts, dur=dur)
    if attrs:
        rec["attrs"] = attrs
    return rec


def test_folded_stacks_and_self_time(tmp_path):
    recs = [
        _span("serve", 0.0, 1.0, id="root"),
        _span("engine.run", 0.1, 0.6, id="eng", parent="root",
              backend="reference"),
        _span("engine.run", 0.8, 0.1, id="eng2", parent="root",
              backend="reference"),
    ]
    prof = Profile(recs)
    folded = prof.folded()
    assert folded["serve"] == pytest.approx(0.3)       # 1.0 - 0.6 - 0.1
    key = "serve;engine.run[backend=reference]"
    assert folded[key] == pytest.approx(0.7)
    out = tmp_path / "profile.folded"
    prof.write_folded(str(out))
    assert f"{key} 700000" in out.read_text()          # integer µs lines


def test_self_time_ignores_cross_thread_children():
    recs = [
        _span("async.run", 0.0, 1.0, id="root", thread=0),
        _span("async.step", 0.0, 0.9, id="w", parent="root", thread=1,
              chunk=0),
    ]
    prof = Profile(recs)
    # the worker runs on its own thread: it owns its time, the parent
    # keeps its full wall (it was genuinely busy dispatching/waiting)
    assert prof.folded()["async.run"] == pytest.approx(1.0)
    assert prof.folded()["async.run;async.step[chunk=0]"] \
        == pytest.approx(0.9)


def test_hotspots_carry_dispatch_sync_split():
    recs = [_span("engine.run", 0.0, 1.0, id="e", backend="pallas")]
    recs[0]["dispatch_s"] = 0.7
    recs[0]["sync_s"] = 0.2
    h = Profile(recs).hotspots(1)[0]
    assert h["frame"] == "engine.run[backend=pallas]"
    assert h["dispatch_s"] == pytest.approx(0.7)
    assert h["sync_s"] == pytest.approx(0.2)


def test_critical_path_names_the_bounding_chunk():
    # chunk 1's chain finishes last and dominates wall-clock
    recs = [
        _span("async.step", 0.0, 0.2, id="a0", thread=1, chunk=0,
              epoch=0),
        _span("async.step", 0.0, 0.5, id="b0", thread=2, chunk=1,
              epoch=0),
        _span("async.step", 0.5, 0.5, id="b1", thread=2, chunk=1,
              epoch=1),
        _span("async.step", 0.21, 0.2, id="a1", thread=1, chunk=0,
              epoch=1),
    ]
    cp = Profile(recs).critical_path()
    assert cp.bounding_chunk == 1
    assert cp.length_s == pytest.approx(1.0)
    assert "chunk 1" in cp.describe()


def test_real_async_run_profiles_end_to_end(fresh_obs):
    from repro.asyncexec import AsyncPsiDriver
    from repro.core import heterogeneous
    from repro.graphs import powerlaw_configuration
    g = powerlaw_configuration(300, 1800, seed=3)
    drv = AsyncPsiDriver(g, heterogeneous(300, seed=4), num_chunks=3,
                         tau=2)
    drv.run(tol=1e-6, max_iter=2000)
    prof = Profile.from_tracer(obs.trace.get_tracer())
    assert any(r["name"] == "async.step" for r in prof.records)
    steps = [r for r in prof.records if r["name"] == "async.step"]
    assert all("chunk" in (r.get("attrs") or {}) for r in steps)
    assert any((r.get("attrs") or {}).get("epoch", -1) >= 0
               for r in steps)
    cp = prof.critical_path()
    assert cp.steps and 0.0 < cp.length_s <= cp.wall_s + 1e-9
    assert sum(cp.chunk_share.values()) == pytest.approx(cp.length_s)


# --------------------------------------------------------------------- #
# convergence watch
# --------------------------------------------------------------------- #
def _resolve_record(gaps, *, backend="reference", accepted=0, rejected=0):
    rec = ResolveRecord(backend, "_default", 0, max_points=512)
    for t, g in enumerate(gaps):
        rec.add_point(t, raw=g)
    rec.aitken_accepted = accepted
    rec.aitken_rejected = rejected
    return rec


def test_watch_flags_contraction_drift(fresh_obs):
    w = ConvergenceWatch(baseline=2, rho_drift=0.05)
    healthy = [0.5 ** i for i in range(10)]           # rho 0.5
    for _ in range(2):
        w.observe_record(_resolve_record(healthy))
    assert not w.advice()
    w.observe_record(_resolve_record([0.9 ** i for i in range(10)]))
    adv = w.advice()
    assert adv.sync_sweep and "rho_drift" in adv.reasons


def test_watch_flags_gap_plateau(fresh_obs):
    w = ConvergenceWatch()
    w.observe_record(_resolve_record([1e-3] * 8))
    assert "gap_plateau" in w.advice().reasons


def test_watch_flags_aitken_shift(fresh_obs):
    w = ConvergenceWatch(baseline=2, aitken_shift=0.35)
    for _ in range(2):
        w.observe_record(_resolve_record([], accepted=9, rejected=1))
    w.observe_record(_resolve_record([], accepted=2, rejected=8))
    assert "aitken_shift" in w.advice().reasons


def test_watch_flags_certificate_storm_onset(fresh_obs):
    class Report:
        rejected_certificates = 30
    w = ConvergenceWatch(cert_storm=50, storm_frac=0.5)
    w.observe_report(Report())
    adv = w.advice()
    assert adv.tighten_tau and "cert_storm_onset" in adv.reasons


def test_watch_projects_alpha_across_the_wall(fresh_obs):
    w = ConvergenceWatch(alpha_max=1.0, alpha_horizon=3)
    for a in (0.80, 0.87, 0.94):      # +0.07/step -> 1.15 in 3 steps
        w.observe_alpha(a)
    adv = w.advice()
    assert adv.sync_sweep and "alpha_drift" in adv.reasons
    # flagged BEFORE the wall: last observed alpha still < alpha_max
    assert w.signals[-1].value == pytest.approx(0.94)


def test_watch_ignores_flat_alpha(fresh_obs):
    w = ConvergenceWatch()
    for a in (0.80, 0.80, 0.80, 0.80):
        w.observe_alpha(a)
    assert not w.advice()


def test_advice_latches_and_consume_rearms(fresh_obs):
    w = ConvergenceWatch()
    w.observe_failure("timeout", "attempt 1")
    assert w.advice() and w.advice()          # peek does not consume
    adv = w.consume_advice()
    assert adv.sync_sweep and adv.reasons == ("attempt_failure",)
    assert not w.advice() and not w.consume_advice()


def test_watch_attach_subscribes_to_the_tracker(fresh_obs):
    from repro.obs import convergence as obs_convergence
    w = ConvergenceWatch().attach()
    try:
        tr = obs_convergence.get_tracker()
        rec = tr.begin("reference")
        for t in range(8):
            rec.add_point(t, raw=1e-3)        # flat -> plateau
        tr.finish(rec, iterations=8, gap=1e-3, converged=False)
        assert "gap_plateau" in w.advice().reasons
    finally:
        w.detach()
    fam = fresh_obs.get("psi_watch_signals_total")
    assert sum(ch.value for _, ch in fam.children()) >= 1
    assert any(e["name"] == "watch_anomaly" for e in obs_log.recent(50))


def test_watch_feeds_preemptive_rechunk_into_the_ladder(fresh_obs):
    from repro.asyncexec import AsyncPsiDriver
    from repro.core import heterogeneous
    from repro.graphs import powerlaw_configuration
    from repro.resilience import ResilientResolver
    g = powerlaw_configuration(300, 1800, seed=3)
    drv = AsyncPsiDriver(g, heterogeneous(300, seed=4), num_chunks=3,
                         tau=2)
    w = ConvergenceWatch(cert_storm=50, storm_frac=0.5)

    class Report:
        rejected_certificates = 40
    w.observe_report(Report())                # tighten_tau advice pending
    res = ResilientResolver(drv, tol=1e-6, max_iter=4000, watch=w)
    out = res.resolve()
    assert res.report.preemptions == ["rechunk"]
    assert res.driver.tau == 0                # staleness bound tightened
    assert not out.degraded and out.escalation == "none"
    fam = fresh_obs.get("psi_resilience_preemptions_total")
    assert fam is not None and \
        fam.labels(action="rechunk").value == 1
    # advice was consumed: a second resolve does not re-preempt
    res.resolve()
    assert res.report.preemptions == ["rechunk"]
