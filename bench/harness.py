"""Run one cell once: build the deployment, warm up, measure, trace, check.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py`` and ``bench/limits/<cell>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import checks, graphs, tracing, work
from bench.reference import Reference, ReferenceStalled
from bench import traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(**kv) -> None:
    print(" ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# --------------------------------------------------------------------- #
# Finding the cell's files by name
# --------------------------------------------------------------------- #
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_files(bench: dict, cell: str) -> tuple[dict, dict, dict, dict]:
    """``(cell entry, config, traffic mix, limits)`` of one cell."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r}; have {sorted(cells)}")
    entry = cells[cell]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = traffic.validate(json.loads(
        (BENCH / "traffic" / f"{entry['traffic']}.json").read_text()))
    limits = json.loads((BENCH / "limits" / f"{cell}.json").read_text())
    return entry, cfg, mix, limits


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end ones untraced, its
    per-layer ones traced."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader(name: str):
    """The ``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------- #
# The service under test
# --------------------------------------------------------------------- #
def program_service(cfg: dict, n, src, dst, lam, mu):
    """``repro.core.PsiService`` on the deployment, as a user builds it."""
    import jax.numpy as jnp
    from repro.core import Activity, PsiService
    from repro.graphs.structure import Graph
    solver = cfg["solver"]
    if solver["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {solver['dtype']!r}")
    return PsiService(Graph(n, src, dst, name=cfg["name"]),
                      Activity(lam, mu), tol=solver["tol"],
                      max_iter=solver["max_iter"],
                      backend=solver["backend"], dtype=jnp.float32)


def describe(svc) -> dict:
    """What the planner chose, and whether the kernels are compiled."""
    eng = svc.engine
    plan = getattr(eng, "plan", None)
    fmt = getattr(eng, "fmt_host", None)
    return dict(backend=svc.backend,
                plan=plan.label() if plan is not None else None,
                interpret=getattr(eng, "interpret", None),
                blocks=getattr(fmt, "num_blocks", None))


# --------------------------------------------------------------------- #
# One request
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Request:
    index: int
    timed: bool
    t_start: float = 0.0
    t_end: float = 0.0
    iterations: int = 0
    psi: np.ndarray | None = None
    # a refresh's parameters, what its reads answered, and the host
    # seconds of each of its phases (patch, solve, read)
    refresh: traffic.Refresh | None = None
    answers: dict | None = None
    phase_s: dict | None = None

    @property
    def latency_s(self) -> float:
        return self.t_end - self.t_start


class Client:
    """Sends a cold mix's requests to the service, one at a time."""

    def __init__(self, svc, mix: dict, cfg: dict, n: int, seed: int):
        import jax
        self.svc = svc
        self.mix = mix
        self.annotate = jax.profiler.TraceAnnotation
        self.sent: list[Request] = []

    def warm_up(self) -> None:
        for _ in range(self.mix["warmup_requests"]):
            # a cold solve capped at one iteration compiles the same loop
            # (tol and max_iter are traced arguments), without its cost
            self.request(timed=False, max_iter=1)

    def request(self, *, timed: bool, max_iter: int | None = None
                ) -> Request:
        """One cold solve from c, with ψ copied to the host."""
        svc = self.svc
        r = Request(index=len(self.sent), timed=timed)
        r.t_start = time.perf_counter()
        with self.annotate("bench.solve"):
            res = svc.engine.run(tol=svc.tol,
                                 max_iter=max_iter or svc.max_iter)
            r.psi = np.asarray(res.psi)
        r.t_end = time.perf_counter()
        r.iterations = int(res.iterations)
        r.phase_s = {"solve": r.latency_s}
        self.sent.append(r)
        return r


class RefreshClient:
    """Sends a refresh mix's requests to the service through its public
    API, one at a time: patch, warm re-solve, reads."""

    def __init__(self, svc, mix: dict, cfg: dict, n: int, seed: int):
        import jax
        self.svc = svc
        self.mix = mix
        self.gen = traffic.RefreshTraffic(mix, n, cfg["activity"],
                                          cfg["structure_seed"], seed)
        self.annotate = jax.profiler.TraceAnnotation
        self.block = jax.block_until_ready
        self.sent: list[Request] = []

    def warm_up(self) -> None:
        """The service's first (cold) solve, then ``warmup_requests``
        refreshes: every program a refresh runs is compiled after it."""
        self.svc.resolve()
        for _ in range(self.mix["warmup_requests"]):
            self.request(timed=False)

    def request(self, *, timed: bool) -> Request:
        """One refresh, timed from the patch's submission to the last
        read's answer; the served ψ is kept by reference."""
        svc = self.svc
        r = Request(index=len(self.sent), timed=timed,
                    refresh=self.gen.request(len(self.sent)))
        q = r.refresh
        r.t_start = time.perf_counter()
        with self.annotate("bench.patch"):
            svc.update_activity(q.users, q.lam, q.mu, resolve=False)
        t_patch = time.perf_counter()
        with self.annotate("bench.solve"):
            svc.resolve()
            # ψ is ready inside the solve's span, so that its device time
            # is the solve's and not the first read's
            self.block(svc.last_result.psi)
        t_solve = time.perf_counter()
        with self.annotate("bench.read"):
            top_ids, top_vals = svc.top_k(q.top_k)
            ranks = svc.rank_of(q.rank_users)
            scores = svc.scores_batch(q.score_users)
        r.t_end = time.perf_counter()
        r.iterations = int(svc.last_result.iterations)
        r.psi = svc.scores()
        r.answers = {"top_k": (np.asarray(top_ids), np.asarray(top_vals)),
                     "rank_of": np.asarray(ranks),
                     "scores_batch": np.asarray(scores)}
        r.phase_s = {"patch": t_patch - r.t_start, "solve": t_solve - t_patch,
                     "read": r.t_end - t_solve}
        self.sent.append(r)
        return r


# each kind of request (``traffic.kind``): the client that sends it and
# the comparison that checks its answers
KINDS = {"cold": (Client, checks.compare),
         "refresh": (RefreshClient, checks.compare_refresh)}


class CompileCounter:
    """Counts XLA compiles (and persistent-cache loads) while it is on."""

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# --------------------------------------------------------------------- #
# The run
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    n: int
    m: int
    setup_s: float
    window_s: float
    requests: list[Request]          # the measured window
    traced: list[Request]            # the traced segment after it
    trace: tracing.Reduced | None
    peak: dict | None


def run_cell(cell: str, *, seed: int, seconds: float, trace: bool,
             t_process: float, bench: dict | None = None,
             make_service=program_service, require_tpu: bool = True,
             save_trace: str | None = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    bench = bench or load_benchmark()
    _, cfg, mix, limits = cell_files(bench, cell)
    dev = jax.devices()[0]
    peak = work.peaks(dev.device_kind) if require_tpu else None
    counter = CompileCounter()

    t0 = time.perf_counter()
    n, src, dst, lam, mu = graphs.build_deployment(cfg, seed)
    t_gen = time.perf_counter()
    svc = make_service(cfg, n, src, dst, lam, mu)
    t_svc = time.perf_counter()
    info = describe(svc)
    log(cell=cell, seed=seed, n=n, m=int(src.size), **info)
    if require_tpu and info["interpret"] is not False:
        raise RuntimeError(f"kernels would run in interpret mode: {info}")

    client = KINDS[traffic.kind(mix)][0](svc, mix, cfg, n, seed)
    client.warm_up()
    t_ready = time.perf_counter()
    setup_s = t_ready - t_process
    log(setup_s=f"{setup_s:.3f}", generate_s=f"{t_gen - t0:.3f}",
        service_s=f"{t_svc - t_gen:.3f}", warmup_s=f"{t_ready - t_svc:.3f}",
        before_build_s=f"{t0 - t_process:.3f}")

    # the measured window: closed loop until the first request that ends
    # after `seconds`
    counter.on = True
    window: list[Request] = []
    t_win = time.perf_counter()
    while True:
        window.append(client.request(timed=True))
        if window[-1].t_end - t_win >= seconds:
            break
    window_s = window[-1].t_end - t_win
    counter.on = False
    log(window_s=f"{window_s:.3f}", requests=len(window),
        iterations=sorted({r.iterations for r in window}),
        compiles_in_window=counter.compiles,
        cache_loads_in_window=counter.cache_hits,
        generator_s=f"{window_s - sum(r.latency_s for r in window):.3f}")
    log(**{f"{k}_ms": f"{1e3 * np.mean([r.phase_s[k] for r in window]):.3f}"
           for k in window[0].phase_s})

    traced: list[Request] = []
    reduced = None
    if trace:
        events = trace_segment(client, mix["trace_requests"], traced)
        if save_trace:
            Path(save_trace).write_text(json.dumps(events))
        reduced = reduce_trace(events)

    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))
    log(peak_bytes_in_use=memory_peak)

    run = Run(n=n, m=int(src.size), setup_s=setup_s,
              window_s=window_s, requests=window, traced=traced,
              trace=reduced, peak=peak)
    metrics = {}
    for m in metrics_for(bench, cell, trace):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    # free the program's state before the reference runs
    sent = client.sent
    del client, svc
    gc.collect()
    numbers, failed = check(cfg, mix, sent, n, src, dst, lam, mu, seed)
    verdicts = {k: {"value": v,
                    "limit": limits.get(k, {"limit": 0})["limit"]}
                for k, v in numbers.items()}
    correct = all(v["value"] <= v["limit"] for v in verdicts.values())

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct),
              "attempted": len(window) + len(traced),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.op_seconds],
            "idle_gaps": [[k, v] for k, v in reduced.idle_gaps]}
    result["checks"] = verdicts
    for k, v in verdicts.items():
        print(f"check {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def trace_segment(client, count: int, out: list[Request]) -> dict:
    """``count`` more requests with the profiler on; their trace's events."""
    import jax
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            for _ in range(count):
                out.append(client.request(timed=True))
        finally:
            jax.profiler.stop_trace()
        return tracing.extract(tracing.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def reduce_trace(events: dict) -> tracing.Reduced:
    """The traced segment's numbers; a trace in which no device op falls
    inside a ``bench.solve`` span fails the run, since its device metrics
    would silently be missing."""
    reduced = tracing.reduce(events)
    if reduced is None or reduced.device_s_in.get("bench.solve", 0.0) <= 0:
        raise RuntimeError(
            "the trace holds no device op inside a bench.solve span: "
            f"{len(events['spans'])} bench spans; planes and lines "
            f"{events.get('planes')}")
    log(traced_window_s=f"{reduced.window_s:.4f}",
        busy_s=f"{reduced.busy_s:.4f}",
        idle_share=f"{reduced.idle_share:.4f}")
    return reduced


def check(cfg, mix, sent, n, src, dst, lam, mu, seed):
    """The compared numbers and the count of failed requests."""
    t0 = time.perf_counter()
    ref = Reference(n, src, dst, lam, mu)
    compare = KINDS[traffic.kind(mix)][1]
    try:
        numbers = compare(ref, sent, sample_size=mix["check_sample"],
                          seed=seed)
    except ReferenceStalled as e:
        log(reference="stalled", error=str(e))
        return {"reference_stalled": 1}, 0
    log(reference_s=f"{time.perf_counter() - t0:.3f}",
        reference_iterations=ref.iterations)
    return numbers, int(numbers.pop("failed"))
