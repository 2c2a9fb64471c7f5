"""Telemetry plane units: exposition golden, span nesting + thread
safety under the async scheduler, null-path overhead, the retrace guard,
and the instrumented-vs-disabled bitwise parity contract."""
import json
import os
import re
import tempfile
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import convergence as obs_convergence
from repro.obs import log as obs_log
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry, NullRegistry


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
# exposition
# --------------------------------------------------------------------- #
def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests served", ("code",))
    c.labels(code="200").inc()
    c.labels(code="500").inc(2)
    reg.gauge("temperature", "current reading").set(1.5)
    h = reg.histogram("latency_seconds", "request wall",
                      buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    assert reg.to_prometheus() == (
        "# HELP latency_seconds request wall\n"
        "# TYPE latency_seconds histogram\n"
        'latency_seconds_bucket{le="0.1"} 1\n'
        'latency_seconds_bucket{le="1"} 2\n'
        'latency_seconds_bucket{le="+Inf"} 3\n'
        "latency_seconds_sum 5.55\n"
        "latency_seconds_count 3\n"
        "# HELP requests_total requests served\n"
        "# TYPE requests_total counter\n"
        'requests_total{code="200"} 1\n'
        'requests_total{code="500"} 2\n'
        "# HELP temperature current reading\n"
        "# TYPE temperature gauge\n"
        "temperature 1.5\n")


def test_json_exposition_quantiles_and_roundtrip():
    reg = MetricsRegistry()
    h = reg.histogram("h", "", buckets=(1.0, 10.0, 100.0))
    for v in range(1, 101):
        h.observe(float(v))
    doc = json.loads(json.dumps(reg.to_json()))
    ser = doc["h"]["series"][0]
    assert ser["count"] == 100 and ser["min"] == 1.0 and ser["max"] == 100.0
    assert ser["p50"] <= ser["p90"] <= ser["p99"] <= 100.0
    # p50 of 1..100 must land inside the (1, 10] / (10, 100] boundary zone
    assert 10.0 <= ser["p50"] <= 100.0


def test_registry_conflicting_redeclaration_raises():
    reg = MetricsRegistry()
    reg.counter("x", "a counter")
    reg.counter("x")                        # idempotent re-use is fine
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.counter("x", labelnames=("k",))


def test_metrics_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("n", "")
    h = reg.histogram("d", "")

    def work():
        for _ in range(1_000):
            c.inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8_000
    assert reg.get("d").merged().count == 8_000


# --------------------------------------------------------------------- #
# spans
# --------------------------------------------------------------------- #
def test_span_nesting_and_async_thread_safety(fresh_obs):
    from repro.asyncexec import AsyncPsiDriver
    from repro.core import heterogeneous
    from repro.graphs import powerlaw_configuration

    g = powerlaw_configuration(1_000, 6_000, seed=3)
    act = heterogeneous(g.n, seed=4)
    rep = AsyncPsiDriver(g, act, num_chunks=4, tau=2).run(tol=1e-6)
    assert rep.converged
    tracer = obs_trace.get_tracer()
    spans = list(tracer.spans)
    by_id = {s["id"]: s for s in spans}
    steps = [s for s in spans if s["name"] == "async.step"]
    assert steps, "worker threads emitted no async.step spans"
    assert len({s["thread"] for s in steps}) >= 2, \
        "async.step spans should come from multiple worker threads"
    for s in spans:
        if s.get("parent"):
            parent = by_id[s["parent"]]
            # nesting is per-thread: a child lives inside its parent's
            # window on the shared clock
            assert parent["thread"] == s["thread"]
            assert parent["ts"] <= s["ts"] + 1e-9
            assert s["depth"] == parent["depth"] + 1
    # the driver's convergence record carries a real gap trajectory
    recs = obs_convergence.get_tracker().series()
    drv = [r for r in recs if r.backend == "async_driver"]
    assert drv and len(drv[-1].points) >= 1
    assert drv[-1].converged


def test_span_measures_without_tracer():
    """Spans on the NULL_TRACER still measure (drivers consume
    duration_s) — they just record nothing."""
    with obs_trace.span("anything") as sp:
        time.sleep(0.01)
    assert sp.duration_s >= 0.008


def test_tracer_jsonl_and_chrome_export(tmp_path):
    path = str(tmp_path / "t.jsonl")
    prev = obs.configure(tracer=obs.Tracer(path))
    try:
        with obs_trace.span("outer", k=1):
            with obs_trace.span("inner"):
                pass
        obs_trace.get_tracer().flush()
    finally:
        obs.restore(prev)
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["name"] for ln in lines] == ["inner", "outer"]
    assert lines[0]["parent"] == lines[1]["id"]
    chrome = str(tmp_path / "t.chrome.json")
    tracer = obs.Tracer(None)
    prev = obs.configure(tracer=tracer)
    try:
        with obs_trace.span("solo"):
            pass
    finally:
        obs.restore(prev)
    tracer.export_chrome(chrome)
    doc = json.load(open(chrome))
    assert any(e.get("name") == "solo" for e in doc["traceEvents"])


# --------------------------------------------------------------------- #
# disabled path
# --------------------------------------------------------------------- #
def test_null_registry_is_cheap_and_inert():
    prev = obs.disable()
    try:
        assert not obs.enabled()
        reg = obs_metrics.get_registry()
        assert getattr(reg, "null", False)
        t0 = time.perf_counter()
        for _ in range(200_000):
            obs_metrics.counter("hot_path_total").inc()
        per_op = (time.perf_counter() - t0) / 200_000
        # one attribute access + one no-op call; generous CI bound
        assert per_op < 5e-6, f"null counter costs {per_op * 1e6:.2f}us/op"
        assert reg.to_prometheus() == "" and reg.to_json() == {}
        assert obs_convergence.begin("reference") is None
        obs_convergence.finish(None, gap=0.0)      # must not raise
    finally:
        obs.restore(prev)


# --------------------------------------------------------------------- #
# retrace guard
# --------------------------------------------------------------------- #
def test_retrace_guard_counts_forced_recompile(fresh_obs):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        return x * 2.0

    guarded = obs_trace.retrace_guard(f, name="unit.f")
    guarded(jnp.ones((3,)))                 # first compile: expected
    assert guarded.retraces == 0
    guarded(jnp.ones((4,)))                 # shape change: silent retrace
    assert guarded.retraces == 1
    assert fresh_obs.value("psi_retraces_total", fn="unit.f") == 1.0
    events = obs_log.recent(10, name="retrace")
    assert events and events[-1]["fn"] == "unit.f"


# --------------------------------------------------------------------- #
# structured warnings
# --------------------------------------------------------------------- #
def test_obs_log_warn_still_warns(fresh_obs):
    with pytest.warns(RuntimeWarning, match="something torn"):
        obs_log.warn("unit_event", "something torn", step=9)
    ev = obs_log.recent(5, name="unit_event")
    assert ev and ev[-1]["level"] == "warning"
    assert ev[-1]["step"] == 9
    assert fresh_obs.value("obs_events_total",
                           event="unit_event", level="warning") == 1.0


def test_checkpoint_corruption_routes_through_obs(fresh_obs):
    from repro.ckpt import checkpoint
    import jax.numpy as jnp

    with tempfile.TemporaryDirectory() as d:
        checkpoint.save(d, 1, dict(x=jnp.ones((3,))))
        checkpoint.save(d, 2, dict(x=jnp.ones((3,))))
        with open(os.path.join(d, "step_00000002", "MANIFEST.json"),
                  "w") as f:
            f.write("{ torn")
        with pytest.warns(RuntimeWarning, match="corrupt or incomplete"):
            assert checkpoint.latest_step(d) == 1
    assert obs_log.recent(5, name="ckpt_corrupt_step")


# --------------------------------------------------------------------- #
# parity: instrumentation only ever reads
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_instrumented_psi_bitwise_parity(backend):
    from repro.core import heterogeneous, make_engine
    from repro.graphs import powerlaw_configuration

    g = powerlaw_configuration(800, 4_800, seed=11)
    act = heterogeneous(g.n, seed=12)

    def solve():
        return np.array(
            make_engine(backend, graph=g, activity=act).run(tol=1e-8).psi,
            copy=True)

    prev = obs.configure(registry=MetricsRegistry(),
                         tracer=obs.Tracer(None),
                         tracker=obs.ConvergenceTracker())
    try:
        live = solve()
        assert obs_metrics.get_registry().value(
            "psi_resolves_total", backend=backend) == 1.0
    finally:
        obs.restore(prev)
    prev = obs.disable()
    try:
        dark = solve()
    finally:
        obs.restore(prev)
    assert np.array_equal(live, dark), \
        "instrumentation changed the computed fixed point"


def test_query_metrics_and_cache_hit_ratio(fresh_obs):
    from repro.core import PsiService, heterogeneous
    from repro.graphs import powerlaw_configuration

    g = powerlaw_configuration(600, 3_600, seed=21)
    act = heterogeneous(g.n, seed=22)
    svc = PsiService(g, act, tol=1e-8, backend="reference")
    svc.top_k(3)                             # miss: first resolve
    svc.top_k(3)                             # hit: cached ranking
    svc.scores_batch(np.arange(4))           # hit
    reg = fresh_obs
    hits = reg.value("psi_query_cache_total", result="hit") or 0
    misses = reg.value("psi_query_cache_total", result="miss") or 0
    assert misses >= 1 and hits >= 2
    pooled = reg.get("psi_query_seconds").merged()
    assert pooled.count == hits + misses
    assert pooled.quantile(0.5) <= pooled.quantile(0.99)


# --------------------------------------------------------------------- #
# the solve's layers, as a profiler sees them
# --------------------------------------------------------------------- #
def _host_spans(trace_dir) -> dict:
    """``{name: (start_ns, end_ns)}`` of the program's spans (the host
    events marked ``PROFILER_MARK``) on the host plane of the trace under
    ``trace_dir``."""
    import glob

    import jax
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    mark = set(obs.trace.PROFILER_MARK.items())
    return {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if mark <= set(e.stats)}


@pytest.mark.parametrize("plane", ["dark", "live"])
def test_spans_land_on_the_profiler_host_plane(tmp_path, plane):
    """Every Span is also a profiler annotation: a trace of one pallas
    solve shows ``engine.loop`` and then ``engine.epilogue`` inside
    ``engine.run`` (and, with the obs plane live, its ``engine.sync``)."""
    import jax

    from repro.core import PsiService, heterogeneous
    from repro.graphs import powerlaw_configuration

    g = powerlaw_configuration(300, 1_800, seed=3)
    svc = PsiService(g, heterogeneous(g.n, seed=4), tol=1e-6,
                     backend="pallas")
    svc.scores()                                 # compile outside the trace
    prev = (obs.disable() if plane == "dark" else
            obs.configure(registry=MetricsRegistry(),
                          tracer=obs.Tracer(None),
                          tracker=obs.ConvergenceTracker()))
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            svc.update_activity(np.arange(5), lam=np.full(5, 0.5))
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.restore(prev)
    spans = _host_spans(str(tmp_path))
    inner = ["engine.loop", "engine.epilogue"] + \
        (["engine.sync"] if plane == "live" else [])
    assert set(spans) == {"service.update_activity", "service.resolve",
                          "engine.run", *inner}

    def inside(name, outer):
        lo, hi = spans[outer]
        return lo <= spans[name][0] <= spans[name][1] <= hi

    assert inside("service.resolve", "service.update_activity")
    assert inside("engine.run", "service.resolve")
    assert all(inside(name, "engine.run") for name in inner)
    assert spans["engine.loop"][1] <= spans["engine.epilogue"][0]


def test_solve_layers_are_named_in_the_compiled_programs():
    """The ``psi_gather`` kernel carries the ``psi.gather`` scope and the
    kernel the name ``psi_power_step`` in the loop's op metadata, and no
    XLA gather is left in the loop; the epilogue's ops carry
    ``psi.epilogue``. A profiler trace finds the layers by these."""
    import jax.numpy as jnp

    from repro.core import heterogeneous, make_engine
    from repro.core.operators import _psi_epilogue
    from repro.graphs import powerlaw_configuration

    g = powerlaw_configuration(300, 1_800, seed=3)
    eng = make_engine("pallas", graph=g, activity=heterogeneous(g.n, seed=4))
    loop = eng._loop.lower(eng._step_args(), eng._to_native(eng.ops.c),
                           eng._scale(), jnp.float32(1e-6),
                           jnp.int32(5)).compile().as_text()
    assert "psi.gather/jit(gather_call)/psi_gather/" in loop
    assert not re.search(r"\sgather\(", loop)
    assert "/psi_power_step/" in loop
    epilogue = _psi_epilogue.lower(eng.ops, eng.ops.c).compile().as_text()
    assert 'op_name="jit(_psi_epilogue)/psi.epilogue/' in epilogue
