"""repro.obs — the unified telemetry plane.

Three pillars, one switchboard:

- :mod:`repro.obs.metrics` — process-wide counters/gauges/histograms with
  Prometheus-text and JSON exposition (``NullRegistry`` when disabled).
- :mod:`repro.obs.trace` — nestable spans on one shared clock, JSONL +
  Chrome ``trace_event`` export, and the jit :func:`retrace_guard`.
- :mod:`repro.obs.convergence` — per-resolve gap/certificate trajectories.

The analysis-and-control layer on top of the measurements:

- :mod:`repro.obs.slo` — declarative SLOs, error budgets, multi-window
  burn-rate alerts (served at ``/slo`` on the exposition server).
- :mod:`repro.obs.profile` — span-stream profiler: folded flamegraph
  stacks, per-backend cost attribution, async critical-path extraction.
- :mod:`repro.obs.watch` — online convergence anomaly detection feeding
  pre-emptive advice into the resilience ladder.

Instrumentation sites throughout the stack call the cheap module-level
helpers (``metrics.counter(...)``, ``trace.span(...)``,
``convergence.record_gap(...)``); :func:`configure` swaps the process
sinks behind them. The default state is metrics ON (pure host-side
Python, no device syncs) with tracing and convergence recording ON in
their bounded in-memory forms — :func:`disable` swaps every sink for its
null twin so the hot path costs one attribute read + no-op call.

``python -m repro.obs.check`` self-tests the plane end to end.
"""
from __future__ import annotations

import json as _json

from . import calibrate, convergence, explain, log, metrics, profile, slo, \
    trace, watch
from .calibrate import CalibrationStore
from .convergence import ConvergenceTracker, NULL_TRACKER
from .env import environment_fingerprint
from .explain import DecisionLog, DecisionRecord, NULL_DECISIONS
from .metrics import MetricsRegistry, NullRegistry, start_http_server
from .profile import Profile
from .slo import SLO, SLOEngine, default_slos
from .trace import NULL_TRACER, Span, Tracer, retrace_guard, span
from .watch import ConvergenceWatch

__all__ = [
    "metrics", "trace", "convergence", "log",
    "slo", "profile", "watch", "explain", "calibrate",
    "MetricsRegistry", "NullRegistry", "Tracer", "Span",
    "ConvergenceTracker", "span", "retrace_guard",
    "SLO", "SLOEngine", "default_slos", "Profile", "ConvergenceWatch",
    "DecisionLog", "DecisionRecord", "CalibrationStore",
    "environment_fingerprint", "start_http_server",
    "configure", "disable", "enabled", "dump",
]


def enabled() -> bool:
    """True when the metrics plane is live (not the NullRegistry)."""
    return metrics.enabled()


def configure(*, registry: MetricsRegistry | None = None,
              trace_out: str | None = None,
              tracer: Tracer | None = None,
              tracker: ConvergenceTracker | None = None,
              decisions: DecisionLog | None = None) -> dict:
    """Install fresh sinks; returns the previous ones (for restoring).

    ``trace_out`` is a convenience: a path builds ``Tracer(trace_out)``.
    """
    prev = {"registry": metrics.get_registry(),
            "tracer": trace.get_tracer(),
            "tracker": convergence.get_tracker(),
            "decisions": explain.get_log()}
    if registry is not None:
        metrics.set_registry(registry)
    if tracer is None and trace_out is not None:
        tracer = Tracer(trace_out)
    if tracer is not None:
        trace.set_tracer(tracer)
    if tracker is not None:
        convergence.set_tracker(tracker)
    if decisions is not None:
        explain.set_log(decisions)
    return prev


def disable() -> dict:
    """Swap every sink for its null twin (one-branch hot path); returns
    the previous sinks so callers can restore them.

    The calibration store is *not* a sink: it is a planner input, so the
    plan chosen with observability disabled matches the instrumented one.
    """
    return configure(registry=NullRegistry(), tracer=NULL_TRACER,
                     tracker=NULL_TRACKER, decisions=NULL_DECISIONS)


def restore(prev: dict) -> None:
    """Undo a :func:`configure`/:func:`disable` using its return value."""
    metrics.set_registry(prev["registry"])
    trace.set_tracer(prev["tracer"])
    convergence.set_tracker(prev["tracker"])
    if "decisions" in prev:
        explain.set_log(prev["decisions"])


def dump(path: str | None = None) -> dict:
    """One self-describing snapshot: fingerprint + metrics + convergence
    trajectories (+ recent structured events). Optionally written to
    ``path`` as JSON."""
    snap = {
        "fingerprint": environment_fingerprint(),
        "metrics": metrics.get_registry().to_json(),
        "convergence": convergence.get_tracker().to_json(),
        "events": log.recent(200),
        "decisions": explain.get_log().to_json(),
        "calibration": calibrate.get_store().to_json(),
    }
    if path is not None:
        with open(path, "w") as f:
            _json.dump(snap, f, indent=1, default=str)
    return snap
