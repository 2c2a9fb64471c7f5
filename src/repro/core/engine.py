"""Unified Power-ψ solver abstraction: one protocol, five backends.

Before this module the repo had four disjoint solver loops (``power_psi``,
``kernels.ops.PsiKernelEngine``, ``DistributedPsi.run_to_convergence`` and the
``PsiService`` rebuild path), each with its own while-loop, convergence rule
and warm-start story. ``PsiEngine`` folds them behind one contract:

    prepare(graph, activity) -> EngineState     # build operators, s₀ = c
    step(state) -> EngineState                  # one Alg. 2 iteration
    run(tol=..., max_iter=..., s0=...) -> PsiResult
    epilogue(s) -> psi                          # ψᵀ = (sᵀB + dᵀ)/N

Backends are registered by name and constructed through
:func:`make_engine`:

  * ``reference``   — the edge-form ``segment_sum`` iteration of
    :mod:`repro.core.power_psi` (works everywhere, float64-capable).
  * ``pallas``      — the TPU Pallas kernels (interpret mode off-TPU) in one
    of two execution regimes: the fused edge-tile ``power_step`` kernel
    (hyper-sparse graphs) or the BSR/MXU ``bsr_spmv`` kernel (clustered
    graphs); pick with ``regime=`` or hand over a
    :class:`~repro.kernels.autotune.RegimePlan`.
  * ``auto``        — a ``pallas`` engine whose regime and tile parameters
    are chosen per graph by the :mod:`repro.kernels.autotune` planner
    (measured-occupancy cost model, optional one-shot micro-benchmark,
    process-level plan cache).
  * ``accelerated`` — the ``reference`` iteration wrapped in the on-device
    Aitken extrapolation loop (see :func:`_make_accelerated_loop`); any
    other backend opts in with ``accelerate=True``.
  * ``distributed`` — the 2-D block-cyclic ``shard_map`` schedule of
    :class:`repro.core.distributed.DistributedPsi`, driven in host-side
    chunks exactly like ``runtime/psi_driver.py``; ``accelerate=True``
    applies the Aitken jump at chunk granularity
    (:class:`ChunkExtrapolator`).
  * ``async``       — the bounded-staleness overlapped chunk scheduler of
    :mod:`repro.asyncexec`: per-chunk epoch tags, straggler absorption up
    to ``tau`` epochs, termination gated by the stale-corrected Eq. 19
    certificate and sealed by a synchronous verification sweep
    (docs/ASYNC.md).
  * ``push``        — the Gauss-Southwell residual-push solver of
    :mod:`repro.localpush`: work proportional to where residual lives
    (O(Δ·deg) after localized patches, certified top-k early stop via
    ``run_top_k``), with the running bound
    ``‖ψ_exact − ψ̂‖₁ ≤ ‖B‖·‖r‖₁/((1−α)·N)`` as the termination rule
    (docs/LOCALPUSH.md).

All backends share one :class:`ConvergenceCriterion` — ε on ‖B‖·‖Δs‖ per
Eq. 19 — and report interchangeable :class:`~repro.core.power_psi.PsiResult`
values (``s`` always returned in node order so a result from one backend can
warm-start any other). Engines also expose the O(Δ) delta-rebuild hooks
(``patch_activity`` / ``patch_edges``) the serving layer
(:class:`repro.core.incremental.PsiService`) is built on; a hook returns
``False`` when the backend cannot patch incrementally and the caller should
fall back to a full ``prepare``.

Registering a new backend (see docs/ENGINE.md)::

    @register_backend("mine")
    class MyEngine(PsiEngine):
        ...
"""
from __future__ import annotations

import abc
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.structure import Graph
from ..obs import convergence as obs_convergence
from ..obs import log as obs_log
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .activity import Activity
from .operators import HostOperators, PsiOperators
from .power_psi import _NORMS, PsiResult

__all__ = ["ConvergenceCriterion", "EngineState", "PsiEngine",
           "ReferenceEngine", "PallasEngine", "AutoEngine",
           "AcceleratedEngine", "DistributedEngine", "AsyncEngine",
           "ChunkExtrapolator",
           "make_engine", "register_backend", "available_backends",
           "make_reference_step", "make_dense_step", "make_edge_tile_step",
           "make_batched_loop"]


# --------------------------------------------------------------------- #
# Shared convergence contract
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ConvergenceCriterion:
    """Alg. 2 termination rule, identical across backends.

    Stop when ``scale · ‖s_t − s_{t−1}‖_norm ≤ tol`` with ``scale = ‖B‖``
    when ``use_b_norm`` (Eq. 19: the ψ trajectory then moved ≤ tol/N), else
    1. ``matvecs`` accounting is shared too: one sparse mat-vec per
    iteration plus one for the ψ epilogue.
    """

    tol: float = 1e-9
    max_iter: int = 10_000
    norm: str = "l1"
    use_b_norm: bool = True

    def __post_init__(self):
        if self.norm not in _NORMS:
            raise ValueError(f"unknown norm {self.norm!r}; "
                             f"choose from {sorted(_NORMS)}")

    def norm_fn(self):
        return _NORMS[self.norm]

    def scale(self, b_norm) -> float:
        return float(b_norm) if self.use_b_norm else 1.0

    def resolve(self, tol: float | None,
                max_iter: int | None) -> tuple[float, int]:
        return (self.tol if tol is None else float(tol),
                self.max_iter if max_iter is None else int(max_iter))


@dataclasses.dataclass
class EngineState:
    """Backend-agnostic iteration state. ``s`` lives in the backend's native
    layout (node order / padded / sharded src layout)."""

    s: Any
    gap: float = float("inf")
    t: int = 0


# --------------------------------------------------------------------- #
# Protocol + registry
# --------------------------------------------------------------------- #
def _instrument_run(run):
    """Wrap a backend's ``run`` with the telemetry plane (repro.obs).

    Applied automatically by :meth:`PsiEngine.__init_subclass__` to every
    backend that defines its own ``run`` — one instrumentation point for
    all current and future backends, including out-of-package ones like
    ``repro.localpush``. Every resolve runs inside an ``engine.run`` span,
    so a profiler trace always shows it. When every obs sink is null that
    span is all the wrapper adds; otherwise it also keeps a convergence
    record and waits for the result inside an ``engine.sync`` span.
    Instrumentation only *reads* the result (and syncs it, which the
    drivers did anyway), so the returned ψ/s are bitwise identical either
    way.
    """

    @functools.wraps(run)
    def wrapped(self, *args, **kwargs):
        tracker = obs_convergence.get_tracker()
        tracer = obs_trace.get_tracer()
        live = tracker.enabled or tracer.enabled or obs_metrics.enabled()
        with obs_trace.span("engine.run", backend=self.name) as sp:
            if not live:
                return run(self, *args, **kwargs)
            rec = tracker.begin(self.name,
                                tenant=getattr(self, "obs_tenant", None))
            try:
                res = run(self, *args, **kwargs)
            except BaseException:
                tracker.finish(rec, converged=False,
                               duration_s=sp.duration_s)
                raise
            with obs_trace.span("engine.sync"):
                sp.sync(res.s)
        tracker.finish(rec, iterations=int(res.iterations),
                       gap=float(res.gap), converged=bool(res.converged),
                       duration_s=sp.duration_s,
                       psi_error_bound=self.psi_error_bound())
        return res

    wrapped._obs_instrumented = True
    return wrapped


class PsiEngine(abc.ABC):
    """One (graph, activity) pair's solver; see module docstring.

    Loop-shaping options shared by every backend:

    * ``accelerate`` — wrap the backend's step in the on-device Aitken
      extrapolation loop (``distributed`` applies it at chunk granularity).
    * ``extrapolate_every`` — target plain iterations between jump attempts.
    * ``check_every`` — evaluate the convergence gap every k-th iteration;
      the k−1 intermediate gap reductions are dead code XLA eliminates, so
      the O(N) norm is amortized over k steps. ``iterations`` then lands on
      a multiple of k (overshoot < k, never undershoot). Ignored by
      ``distributed`` (its cadence is ``chunk_iters``) and by accelerated
      loops (their verify-after-jump pairing fixes the cadence at 2).
    """

    name: str = "abstract"

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        run = cls.__dict__.get("run")
        if run is not None and not getattr(run, "_obs_instrumented", False):
            cls.run = _instrument_run(run)

    def __init__(self, *, dtype=jnp.float32,
                 criterion: ConvergenceCriterion | None = None,
                 accelerate: bool = False, extrapolate_every: int = 8,
                 check_every: int = 1):
        self.dtype = dtype
        self.criterion = criterion or ConvergenceCriterion()
        self.accelerate = bool(accelerate)
        self.extrapolate_every = int(extrapolate_every)
        self.check_every = max(1, int(check_every))
        self._graph: Graph | None = None
        self._graph_stale = False
        self.host: HostOperators | None = None
        self.ops: PsiOperators | None = None

    @property
    def graph(self) -> Graph | None:
        if self._graph_stale:                # edges patched since last look
            self._graph = self.host.graph()
            self._graph_stale = False
        return self._graph

    # -- lifecycle ------------------------------------------------------ #
    @abc.abstractmethod
    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        """Build device operators; returns the cold-start state (s₀ = c)."""

    @abc.abstractmethod
    def run(self, *, tol: float | None = None, max_iter: int | None = None,
            s0: np.ndarray | jax.Array | None = None) -> PsiResult:
        """Iterate to the criterion; ``s0`` (node order) warm-starts."""

    def epilogue(self, s) -> jax.Array:
        """ψᵀ = (sᵀB + dᵀ)/N from a node-order series vector."""
        return self.ops.psi_epilogue(jnp.asarray(np.asarray(s), self.dtype))

    # -- delta rebuild hooks (serving runtime) -------------------------- #
    def patch_activity(self, users, lam=None, mu=None) -> bool:
        """O(Δ) activity patch; False → caller must re-``prepare``."""
        return False

    def patch_edges(self, src, dst) -> bool:
        """O(Δ) edge insertion; False → caller must re-``prepare``."""
        return False

    def unpatch_edges(self, src, dst) -> bool:
        """Edge *removal* (unfollow tombstones); False → caller must
        re-``prepare`` from a filtered graph. Backends whose device format
        cannot shrink incrementally keep the default."""
        return False

    # -- certified serving (see docs/LOCALPUSH.md) ---------------------- #
    def psi_error_bound(self) -> float | None:
        """Certified per-node ``|ψ_exact − ψ_served|`` bound for the last
        ``run``'s returned ψ, or None when the backend cannot certify one
        (the Eq. 19 gap bounds one step's *movement*, not the distance to
        the fixed point). The ``push`` backend overrides this with its
        residual certificate; :class:`~repro.core.incremental.RankingCache`
        and the stream freshness report consume it."""
        return None

    # -- shared helpers ------------------------------------------------- #
    @property
    def activity(self) -> Activity:
        return self.host.activity()

    def _base_prepare(self, graph: Graph, activity: Activity) -> None:
        self._graph = graph
        self._graph_stale = False
        self.host = HostOperators.from_graph(graph, activity)
        self.ops = self.host.to_device(self.dtype)

    def _install_loops(self, one_step) -> None:
        """Build ``self._loop`` / ``self._step_jit`` from the backend's
        ``one_step(args, s) -> (s_new, raw_gap)`` closure, honoring the
        ``accelerate`` / ``check_every`` loop-shaping options.

        ``one_step`` is also kept on the engine as the public ``one_step``
        attribute: it is *pure* in ``(args, s)`` (operators travel as pytree
        arguments), so callers may ``jax.vmap`` it over a stacked batch of
        same-shape operator pytrees — the contract the multi-tenant fleet
        (:mod:`repro.serving`) builds its batched solver on via
        :func:`make_batched_loop`."""
        self.one_step = one_step
        if self.accelerate:
            loop = _make_accelerated_loop(
                one_step, extrapolate_every=self.extrapolate_every)
        else:
            loop = _make_loop(one_step, check_every=self.check_every)
        # count silent recompiles of the solver loop (e.g. the shape change
        # of a format rebuild after a patch_edges overflow)
        self._loop = obs_trace.retrace_guard(loop, name=f"{self.name}.loop")
        self._step_jit = jax.jit(one_step)

    def _scale(self) -> jax.Array:
        return (self.ops.b_norm if self.criterion.use_b_norm
                else jnp.asarray(1.0, self.ops.dtype))

    def _step_args(self):
        """What the engine's jitted ``one_step(args, s)`` closure consumes."""
        return self.ops

    def step(self, state: EngineState) -> EngineState:
        """One Alg. 2 iteration ``s ← sᵀA + c`` with the shared gap rule."""
        s_new, raw = self._step_jit(self._step_args(), state.s)
        return EngineState(s=s_new, gap=float(self._scale()) * float(raw),
                           t=state.t + 1)

    def _s0_node_order(self, s0) -> jax.Array:
        if s0 is None:
            return self.ops.c
        s0 = jnp.asarray(np.asarray(s0), self.dtype)
        if s0.shape != (self.ops.n,):
            raise ValueError(f"s0 must be f[{self.ops.n}] in node order; "
                             f"got {s0.shape}")
        return s0

    def _result(self, psi, s, gap, t, tol) -> PsiResult:
        return PsiResult(psi=psi, s=s, iterations=jnp.asarray(t, jnp.int32),
                         gap=jnp.asarray(gap, self.dtype),
                         converged=jnp.asarray(float(gap) <= tol),
                         matvecs=jnp.asarray(int(t) + 1, jnp.int32))


_REGISTRY: dict[str, type[PsiEngine]] = {}


def register_backend(name: str):
    """Class decorator: make the engine constructible by ``make_engine(name)``."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def _ensure_plugin_backends() -> None:
    """Import out-of-package backends that self-register on import.

    ``repro.localpush`` imports this module, so a bottom-of-file import
    here would deadlock whenever ``repro.localpush`` is the entry point
    (its partially-initialized module would be re-entered before
    ``PushEngine`` exists). Deferring to first registry *use* keeps both
    import orders cycle-free."""
    from .. import localpush  # noqa: F401  (registers backend="push")


def available_backends() -> tuple[str, ...]:
    _ensure_plugin_backends()
    return tuple(sorted(_REGISTRY))


def _accepted_options(cls: type[PsiEngine]) -> set[str]:
    """Every named keyword the backend's ``__init__`` chain accepts."""
    import inspect
    names: set[str] = set()
    for klass in cls.__mro__:
        init = klass.__dict__.get("__init__")
        if init is None:
            continue
        for p in inspect.signature(init).parameters.values():
            if p.name != "self" and p.kind in (p.KEYWORD_ONLY,
                                               p.POSITIONAL_OR_KEYWORD):
                names.add(p.name)
    return names


def make_engine(backend: str = "reference", *, graph: Graph | None = None,
                activity: Activity | None = None, **opts) -> PsiEngine:
    """Factory: construct (and, when given a graph, prepare) a backend."""
    _ensure_plugin_backends()
    try:
        cls = _REGISTRY[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"available: {available_backends()}") from None
    unknown = set(opts) - _accepted_options(cls)
    if unknown:
        # a mistyped option — or an option that belongs to a different
        # backend (e.g. mesh= on reference); point at the full registry
        raise ValueError(
            f"unknown engine option(s) {sorted(unknown)} for backend "
            f"{backend!r} (accepts: {sorted(_accepted_options(cls))}); "
            f"available backends: {available_backends()}")
    engine = cls(**opts)
    if graph is not None:
        if activity is None:
            raise ValueError("graph given without activity")
        engine.prepare(graph, activity)
    return engine


# --------------------------------------------------------------------- #
# Shared while-loop builders — operators travel as pytree *arguments* so a
# delta patch never retraces: the jit cache keys on array shapes only
# (activity patches and sentinel-slot edge inserts preserve shapes).
# --------------------------------------------------------------------- #
def _make_loop(step_with_gap, *, check_every: int = 1):
    """``step_with_gap(args, s) -> (s_new, raw_gap)`` →
    jitted ``loop(args, s0, scale, tol, max_iter) -> (s, gap, t)``.

    With ``check_every=k`` each while-loop body advances k iterations and
    only the k-th raw gap feeds the termination test — the k−1 discarded
    gaps are dead code, so backends whose norm is a separate O(N) reduce
    (``reference``, the BSR regime) pay for it once per k steps. ``t``
    advances in multiples of k (it can overshoot the minimal iteration
    count by < k, never undershoot the tolerance).
    """
    k = max(1, int(check_every))

    @jax.jit
    def loop(args, s0, scale, tol, max_iter):
        def cond(st):
            _, gap, t = st
            return (gap > tol) & (t < max_iter)

        def body(st):
            s, _, t = st
            for _ in range(k - 1):          # unrolled; gaps DCE'd by XLA
                s, _ = step_with_gap(args, s)
            s_new, raw = step_with_gap(args, s)
            return s_new, scale * raw, t + k

        return jax.lax.while_loop(
            cond, body, (s0, jnp.asarray(jnp.inf, s0.dtype),
                         jnp.asarray(0, jnp.int32)))

    return loop


def make_batched_loop(step_with_gap, *, check_every: int = 1):
    """Vmapped, convergence-masked fleet loop over independent lanes.

    ``step_with_gap`` is the same pure ``(args, s) -> (s_new, raw_gap)``
    closure the solo loops consume (an engine's public ``one_step``); every
    leaf of ``args`` and ``s`` gains a leading lane axis.  Returns a jitted

        loop(args, s0, scale, tol, max_iter, active0) -> (s, gap, t)

    with per-lane ``scale`` / ``gap`` / ``t``.  Each lane runs the solo
    termination rule independently: a lane whose gap crosses ``tol`` (or
    whose ``t`` hits ``max_iter``) *freezes* — ``jnp.where`` keeps its
    series vector bitwise intact while the remaining lanes keep stepping —
    and the whole loop exits when no lane is active.  ``active0`` masks
    lanes out from the start (clean tenants sharing a bucket with a dirty
    one never move at all), which is what makes a converged tenant's ψ
    bit-stable across its neighbours' re-solves.

    Per-lane iteration counts match the solo ``_make_loop`` semantics,
    including the ``check_every=k`` cadence (``t`` lands on a multiple of
    k for every lane that ran).
    """
    k = max(1, int(check_every))
    vstep = jax.vmap(step_with_gap)

    @jax.jit
    def loop(args, s0, scale, tol, max_iter, active0):
        lane_shape = (s0.shape[0],) + (1,) * (s0.ndim - 1)

        def cond(st):
            return jnp.any(st[-1])

        def body(st):
            s, gap, t, active = st
            s_k = s
            for _ in range(k - 1):          # unrolled; gaps DCE'd by XLA
                s_k, _ = vstep(args, s_k)
            s_new, raw = vstep(args, s_k)
            gap_new = scale * raw
            s_next = jnp.where(active.reshape(lane_shape), s_new, s)
            gap_next = jnp.where(active, gap_new, gap)
            t_next = jnp.where(active, t + k, t)
            active_next = active & (gap_new > tol) & (t_next < max_iter)
            return s_next, gap_next, t_next, active_next

        lanes = s0.shape[0]
        s, gap, t, _ = jax.lax.while_loop(
            cond, body,
            (s0, jnp.full((lanes,), jnp.inf, s0.dtype),
             jnp.zeros((lanes,), jnp.int32), active0))
        return s, gap, t

    return loop


def make_reference_step(norm: str = "l1"):
    """The pure Alg. 2 step ``(PsiOperators, s) -> (s_new, raw_gap)``.

    Stateless and therefore vmappable: stack the data fields of several
    same-shape :class:`~repro.core.operators.PsiOperators` along a leading
    lane axis (meta ``n`` / ``m`` shared) and the step batches.  Padded
    lanes are inert by construction — zero-rate pad nodes keep ``s = 0``
    and sentinel edges (``dst == n``) are dropped by the segment-sum.
    """
    nrm = _NORMS[norm]

    def one_step(ops, s):
        s_new = ops.mu * ops.push(s) + ops.c
        return s_new, nrm(s_new - s)

    return one_step


def make_dense_step(norm: str = "l1"):
    """The pure dense-matvec Alg. 2 step over ``(E, 1/w, μ, c)`` args.

    ``E`` is the {0,1} follower→leader adjacency (``E[j, i] = 1`` iff j
    follows i), so one matvec computes the push ``t = (s ⊙ 1/w) E`` and the
    step is ``μ ⊙ t + c`` — identical math to the edge form, but a single
    (batched) GEMV instead of a gather/scatter chain.  This is the fleet's
    regime for *small* buckets: a stack of tiny tenants turns into one
    ``[B, n, n]`` batched matvec (BLAS on CPU, MXU on TPU), which beats B
    independent scatter pipelines by a wide margin exactly where the
    multi-tenant batching case lives.  O(n²) memory per lane — the fleet
    only auto-selects it under its ``dense_max_n`` threshold.
    """
    nrm = _NORMS[norm]

    def one_step(args, s):
        E, inv_w, mu, c = args
        s_new = mu * ((s * inv_w) @ E) + c
        return s_new, nrm(s_new - s)

    return one_step


def make_edge_tile_step(interpret: bool):
    """The pure fused edge-tile step over ``(fmt, 1/w, μ, c)`` args.

    Same calling convention as :func:`make_reference_step` but in the
    pallas edge-tile regime's native padded ``[1, n_pad]`` layout; the args
    tuple is ``(DeviceEdgeTiles, inv_w_gather, mu_pad, c_pad)``.  The
    pallas calls (the ``psi_gather`` gather and the fused step) batch
    under ``jax.vmap`` (the batch axis becomes a grid dimension), which is
    how the fleet runs many tenants per device through one launch.
    """
    from ..kernels.ops import power_step

    def one_step(args, s):
        fmt, inv_w_g, mu_pad, c_pad = args
        return power_step(s, inv_w_g, mu_pad, c_pad, fmt,
                          interpret=interpret)

    return one_step


def _make_accelerated_loop(step_with_gap, *, extrapolate_every: int = 8):
    """Aitken / geometric-series extrapolation around *any* backend step.

    Same calling convention as :func:`_make_loop`. Each while-loop body
    consumes exactly two mat-vecs and advances either two plain iterations
    or one extrapolated jump plus its verification step:

        s₁ = step(s);  Δ = s₁ − s;  r = ‖Δ_t‖/‖Δ_{t−1}‖
        s_x = s₁ + Δ · r/(1−r)      every ~extrapolate_every iterations,
                                    while contracting (0 < r < 0.999) and
                                    far from tolerance (gap > 100·tol)
        s₂ = step(s_x)              # verification (or second plain step)

    The termination gap is *always* ``scale·‖s₂ − s_x‖`` — measured across
    a genuine plain iteration — so the Eq. 19 guarantee survives every
    jump; the whole loop is one ``lax.while_loop`` on device (no host sync
    per jump). A jump that fails to shrink the gap is reverted and disables
    all future jumps (degrades to plain Power-ψ at one wasted mat-vec); a
    stalled ratio (r ≈ 1, a floating-point period-2 cycle) triggers a
    Krasnoselskii averaging kick, which is always safe for a contraction.

    The returned ``t`` counts mat-vecs actually consumed. Precision note:
    near a dtype's fixed-point floor a jump can land in a basin whose plain
    fp32 iteration limit-cycles at ‖Δs‖ ≈ 1e-6; request tolerances
    ≥ ~100·ulp for fp32, or run float64 as the paper's ε = 1e-9 sweeps do.
    """
    kb = max(1, int(extrapolate_every) // 2)  # loop bodies between attempts

    @jax.jit
    def loop(args, s0, scale, tol, max_iter):
        def cond(st):
            _, _, gap, t, _, _ = st
            return (gap > tol) & (t < max_iter)

        def body(st):
            s, prev_dn, _, t, j, enabled = st
            s1, raw1 = step_with_gap(args, s)
            delta = s1 - s
            gap_plain = scale * raw1
            r = raw1 / jnp.maximum(prev_dn, 1e-30)
            far = gap_plain > 100.0 * tol
            do_jump = ((j % kb == kb - 1) & (r > 0.0) & (r < 0.999)
                       & far & enabled)
            jump = jnp.where(do_jump, r / (1.0 - r), 0.0)
            s_x = s1 + delta * jump           # == s₁ when not jumping
            s2, raw2 = step_with_gap(args, s_x)
            gap_ver = scale * raw2
            bad = do_jump & (gap_ver >= gap_plain)
            enabled = enabled & ~bad
            s_next = jnp.where(bad, s1, s2)
            gap = jnp.where(bad, gap_plain, gap_ver)
            dn_next = jnp.where(bad, raw1, raw2)
            stall = (~do_jump) & (r > 0.999) & jnp.isfinite(r)
            s_next = jnp.where(stall, 0.5 * (s_x + s2), s_next)
            return s_next, dn_next, gap, t + 2, j + 1, enabled

        s, _, gap, t, _, _ = jax.lax.while_loop(
            cond, body,
            (s0, jnp.asarray(jnp.inf, s0.dtype),
             jnp.asarray(jnp.inf, s0.dtype), jnp.asarray(0, jnp.int32),
             jnp.asarray(0, jnp.int32), jnp.asarray(True)))
        return s, gap, t

    return loop


class ChunkExtrapolator:
    """Host-side Aitken jump between fixed-shape device chunks.

    The ``distributed`` backend (and ``runtime/psi_driver.py``) evaluate
    convergence between ``chunk_iters``-step device scans; this helper
    extrapolates across chunk *endpoints*: the per-chunk contraction ratio
    is ρ^chunk_iters, so the remaining tail after chunk t sums to
    Δ_t · r/(1−r) exactly as in the per-iteration loop. Eq. 19 survives
    because the termination gap is always produced by the *next* chunk's
    plain steps (≥ 1 plain iteration after any jump). A chunk whose gap
    fails to shrink disables all future jumps — no revert is needed since
    the chunk's plain steps already re-contracted the iterate.

    **Epoch-consistency guard** (async executors): the geometric-tail
    formula assumes Δ = s_out − s_in spans a *uniform* number of
    contraction applications on every coordinate. Under bounded-staleness
    execution a chunk endpoint can mix per-chunk epochs; callers pass the
    endpoint pair's ``epoch_spread`` (max − min contributing chunk epoch)
    and the extrapolator only jumps on same-epoch pairs (``spread == 0``),
    dropping its ratio history otherwise — a mixed-epoch Δ is not one
    contraction sample and must not seed r.
    """

    def __init__(self, tol: float, *, guard: float = 100.0):
        self.tol = tol
        self.guard = guard
        self.reset()

    def reset(self) -> None:
        """Forget history (e.g. after a checkpoint restore)."""
        self._prev_dn: float | None = None
        self._gap_prev = float("inf")
        self.enabled = True
        self.jumps = 0

    def advance(self, s_in, s_out, gap: float, *, epoch_spread: int = 0):
        """Map a finished chunk (input → output, scaled gap) to the next
        chunk's start vector, possibly extrapolated. ``epoch_spread != 0``
        marks the endpoints as epoch-inconsistent: no jump fires and the
        Δ-ratio history resets (synchronous callers pass the default 0)."""
        if not self.enabled:
            return s_out
        if epoch_spread != 0:
            # mixed-epoch Δ poisons both the ratio history and the
            # gap-progress baseline — drop them, keep only `enabled`
            self._prev_dn = None
            self._gap_prev = float("inf")
            return s_out
        if gap >= self._gap_prev:             # jump/stall did not help
            self.enabled = False
            obs_convergence.record_aitken(False)
            return s_out
        self._gap_prev = gap
        dn = float(jnp.sum(jnp.abs(s_out - s_in)))
        r = 0.0 if not self._prev_dn else dn / self._prev_dn
        self._prev_dn = dn
        if 0.0 < r < 0.999 and gap > self.guard * self.tol:
            self.jumps += 1
            obs_convergence.record_aitken(True)
            return s_out + (s_out - s_in) * (r / (1.0 - r))
        return s_out


# --------------------------------------------------------------------- #
# reference — edge-form segment_sum iteration (power_psi semantics)
# --------------------------------------------------------------------- #
@register_backend("reference")
class ReferenceEngine(PsiEngine):
    """The paper-faithful Alg. 2 loop on :class:`PsiOperators`."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self._install_loops(make_reference_step(self.criterion.norm))

    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        self._base_prepare(graph, activity)
        return EngineState(s=self.ops.c)

    def run(self, *, tol=None, max_iter=None, s0=None) -> PsiResult:
        tol, max_iter = self.criterion.resolve(tol, max_iter)
        s, gap, t = self._loop(
            self.ops, self._s0_node_order(s0), self._scale(),
            jnp.asarray(tol, self.ops.dtype),
            jnp.asarray(max_iter, jnp.int32))
        return self._result(self.ops.psi_epilogue(s), s, gap, t, tol)

    def patch_activity(self, users, lam=None, mu=None) -> bool:
        self.host.patch_activity(users, lam=lam, mu=mu)
        self.ops = self.host.refresh_node_arrays(self.ops, self.dtype)
        return True

    def patch_edges(self, src, dst) -> bool:
        self.host.patch_edges(src, dst)
        self._graph_stale = True
        self.ops = self.host.to_device(self.dtype)   # edge arrays grew
        return True

    def unpatch_edges(self, src, dst) -> bool:
        removed, _ = self.host.remove_edges(src, dst)
        if removed.size:
            self._graph_stale = True
            self.ops = self.host.to_device(self.dtype)  # edge arrays shrank
        return True


@register_backend("accelerated")
class AcceleratedEngine(ReferenceEngine):
    """Aitken-extrapolated ``reference`` iteration — the ROADMAP's fourth
    registered backend. Identical math to the historical
    ``core.accelerated.power_psi_accelerated`` entry point, now expressed
    as the engine-level loop composition every backend can opt into
    (``make_engine("pallas", accelerate=True)``, …).

    ``iterations`` / ``matvecs`` count mat-vecs actually consumed — the
    honest currency an extrapolated loop is judged in.
    """

    def __init__(self, **kw):
        kw["accelerate"] = True
        super().__init__(**kw)


# --------------------------------------------------------------------- #
# pallas — fused TPU kernels in two execution regimes (absorbs
# PsiKernelEngine; BSR promoted from ablation to first-class regime)
# --------------------------------------------------------------------- #
@register_backend("pallas")
class PallasEngine(PsiEngine):
    """Alg. 2 driven by the Pallas TPU kernels.

    Two execution regimes share the engine (see kernels/formats.py and
    docs/AUTOTUNE.md):

    * ``edge_tile`` — the fused ``power_step`` kernel: dst-sorted edge
      blocks scatter into node tiles, the gap is computed on-chip. Native
      state layout is the padded ``[1, n_pad]`` node vector.
    * ``bsr``       — the ``bsr_spmv`` dense-tile MXU kernel with the μ/c
      epilogue and L1 gap composed around it by XLA. Native layout is the
      node-order ``f[n]`` vector.

    Both regimes compute the gap in ``l1`` (the paper's choice), so the
    criterion's norm must be ``l1``. Activity patches refresh only node
    vectors; edge patches go into free sentinel slots (edge-tile, via an
    O(Δ) per-tile free-slot cursor) or existing dense tiles (BSR) and fall
    back to a regime-format rebuild — never a full operator rebuild — when
    a tile/block overflows.
    """

    def __init__(self, *, regime: str = "edge_tile", tile: int = 256,
                 e1: int = 8, e2: int = 128, ts: int = 128, td: int = 128,
                 interpret: bool | None = None, plan=None, **kw):
        super().__init__(**kw)
        if self.criterion.norm != "l1":
            raise ValueError("pallas backend computes the gap in l1; "
                             f"got norm={self.criterion.norm!r}")
        from ..kernels.ops import default_interpret
        self.interpret = (default_interpret() if interpret is None
                          else interpret)
        self.tile, self.e1, self.e2 = tile, e1, e2
        self.ts, self.td = ts, td
        if plan is not None:
            self._apply_plan(plan)
        else:
            self._set_regime(regime)

    # -- regime plumbing ------------------------------------------------ #
    def _apply_plan(self, plan) -> None:
        """Adopt a :class:`~repro.kernels.autotune.RegimePlan`."""
        if plan.regime == "edge_tile":
            self.tile, self.e1, self.e2 = plan.tile, plan.e1, plan.e2
        else:
            self.ts, self.td = plan.ts, plan.td
        self._set_regime(plan.regime)

    def _set_regime(self, regime: str) -> None:
        if regime not in ("edge_tile", "bsr"):
            raise ValueError(f"unknown pallas regime {regime!r}; "
                             "choose edge_tile or bsr")
        self.regime = regime
        interp = self.interpret
        if regime == "edge_tile":
            one_step = make_edge_tile_step(interp)
        else:
            from ..kernels.ops import bsr_spmv

            def one_step(args, s):
                fmt, inv_w, mu, c = args
                s_new = mu * bsr_spmv(s * inv_w, fmt, interpret=interp) + c
                return s_new, jnp.sum(jnp.abs(s_new - s))

        self._install_loops(one_step)

    def _build_format(self, graph: Graph) -> None:
        if self.regime == "edge_tile":
            from ..kernels.formats import build_edge_tiles
            from ..kernels.ops import DeviceEdgeTiles
            self.fmt_host = build_edge_tiles(graph, tile=self.tile,
                                             e1=self.e1, e2=self.e2)
            self.fmt = DeviceEdgeTiles.from_format(self.fmt_host)
            self._rebuild_tile_cursor()
            self._refresh_padded()
        else:
            from ..kernels.formats import build_bsr
            from ..kernels.ops import DeviceBsr
            self.fmt_host = build_bsr(
                graph, ts=self.ts, td=self.td,
                dtype=np.dtype(jnp.dtype(self.dtype).name))
            self.fmt = DeviceBsr.from_format(self.fmt_host)
            self._rebuild_bsr_block_map()

    def _to_native(self, v: jax.Array) -> jax.Array:
        return (self.fmt.pad_node_vector(v) if self.regime == "edge_tile"
                else v)

    def _from_native(self, s: jax.Array) -> jax.Array:
        return s[0, :self.fmt.n] if self.regime == "edge_tile" else s

    # -- lifecycle ------------------------------------------------------ #
    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        self._base_prepare(graph, activity)
        self._build_format(graph)
        return EngineState(s=self._to_native(self.ops.c))

    def _refresh_padded(self) -> None:
        f = self.fmt
        self._mu_pad = f.pad_node_vector(self.ops.mu)
        self._c_pad = f.pad_node_vector(self.ops.c)
        self._inv_w_gather = f.pad_gather_source(self.ops.inv_w)

    def _step_args(self):
        if self.regime == "edge_tile":
            return (self.fmt, self._inv_w_gather, self._mu_pad, self._c_pad)
        return (self.fmt, self.ops.inv_w, self.ops.mu, self.ops.c)

    def run(self, *, tol=None, max_iter=None, s0=None) -> PsiResult:
        tol, max_iter = self.criterion.resolve(tol, max_iter)
        s_init = self._to_native(self._s0_node_order(s0))
        with obs_trace.span("engine.loop"):
            s, gap, t = self._loop(self._step_args(), s_init, self._scale(),
                                   jnp.asarray(tol, self.ops.dtype),
                                   jnp.asarray(max_iter, jnp.int32))
        with obs_trace.span("engine.epilogue"):
            s_n = self._from_native(s)
            psi = self.ops.psi_epilogue(s_n)
        return self._result(psi, s_n, gap, t, tol)

    # -- delta rebuilds ------------------------------------------------- #
    def patch_activity(self, users, lam=None, mu=None) -> bool:
        self.host.patch_activity(users, lam=lam, mu=mu)
        self.ops = self.host.refresh_node_arrays(self.ops, self.dtype)
        if self.regime == "edge_tile":
            self._refresh_padded()
        return True

    def patch_edges(self, src, dst) -> bool:
        src, dst = self.host.patch_edges(src, dst)
        self._graph_stale = True
        if self.regime == "edge_tile":
            self._patch_edges_edge_tile(src, dst)
        else:
            self._patch_edges_bsr(src, dst)
        self.ops = self.host.to_device(self.dtype)   # edge arrays grew
        if self.regime == "edge_tile":
            self._refresh_padded()
        return True

    # -- edge-tile regime: O(Δ) sentinel-slot inserts -------------------- #
    def _rebuild_tile_cursor(self) -> None:
        """Per-tile free-slot cursor, computed once per format build.

        ``build_edge_tiles`` fills each node tile's block span contiguously
        from its first slot, and cursor inserts preserve that invariant —
        so a tile's free sentinel slots are exactly the tail of its span
        and placing an edge is O(1): no per-edge scan over blocks/slots.
        """
        f = self.fmt_host
        used_per_block = (f.src_idx.reshape(f.num_blocks, -1)
                          != f.n).sum(axis=1)
        self._tile_first_block = np.searchsorted(
            f.block_tile, np.arange(f.num_tiles))
        blocks_per_tile = np.bincount(f.block_tile, minlength=f.num_tiles)
        self._tile_capacity = blocks_per_tile.astype(np.int64) * f.eblk
        self._tile_used = np.bincount(
            f.block_tile, weights=used_per_block,
            minlength=f.num_tiles).astype(np.int64)

    def _insert_into_tiles(self, src: np.ndarray, dst: np.ndarray):
        """Place new edges into free (sentinel) slots of their dst tile.

        O(Δ) total via the per-tile cursor. Mutates the host format in
        place and returns the placed ``(block, flat_slot, src_id,
        dst_local)`` tuples, or ``None`` when any tile would overflow (the
        caller rebuilds the format; nothing is mutated in that case)."""
        f = self.fmt_host
        tile, eblk = f.tile, f.eblk
        tiles_of = np.asarray(dst, np.int64) // tile
        need = np.bincount(tiles_of, minlength=f.num_tiles)
        if np.any(self._tile_used + need > self._tile_capacity):
            return None
        flat_src = f.src_idx.reshape(f.num_blocks, -1)
        flat_dstl = f.dst_local.reshape(f.num_blocks, -1)
        placed = []
        for s, d, t in zip(src, dst, tiles_of):
            t = int(t)
            u = int(self._tile_used[t])
            b = int(self._tile_first_block[t]) + u // eblk
            slot = u % eblk
            d_loc = int(d) - t * tile
            flat_src[b, slot] = s
            flat_dstl[b, slot] = d_loc
            placed.append((b, slot, int(s), d_loc))
            self._tile_used[t] = u + 1
        return placed

    def _patch_edges_edge_tile(self, src: np.ndarray,
                               dst: np.ndarray) -> None:
        slots = self._insert_into_tiles(src, dst)
        if slots is None:
            # a tile ran out of sentinel slots — rebuild the edge-tile
            # format only (the operator arrays stay incrementally patched;
            # the shape change means the next run() retraces once)
            self._build_format(self.graph)
        elif slots:
            # fast path: one batched scatter of the new slots into the
            # device-resident format instead of re-uploading all M edges
            b, slot, s_id, d_loc = (np.asarray(x) for x in zip(*slots))
            i, j = np.divmod(slot, self.e2)
            src_idx = self.fmt.src_idx.at[b, i, j].set(
                jnp.asarray(s_id, jnp.int32))
            dst_local = self.fmt.dst_local.at[b, i, j].set(
                jnp.asarray(d_loc, jnp.int32))
            self.fmt = dataclasses.replace(self.fmt, src_idx=src_idx,
                                           dst_local=dst_local)

    # -- BSR regime: dense-tile increments ------------------------------ #
    def _rebuild_bsr_block_map(self) -> None:
        f = self.fmt_host
        self._bsr_blocks = {
            (int(st), int(dt)): b
            for b, (st, dt) in enumerate(zip(f.src_tile, f.dst_tile))}

    def _patch_edges_bsr(self, src: np.ndarray, dst: np.ndarray) -> None:
        if src.size == 0:
            return
        f = self.fmt_host
        st = np.asarray(src, np.int64) // f.ts
        dt = np.asarray(dst, np.int64) // f.td
        if any((int(a), int(b)) not in self._bsr_blocks
               for a, b in zip(st, dt)):
            # a brand-new (src_tile, dst_tile) block — rebuild the BSR
            # format (shape change → one retrace), never the operators
            self._build_format(self.graph)
            return
        b = np.asarray([self._bsr_blocks[(int(a), int(c))]
                        for a, c in zip(st, dt)])
        r = np.asarray(src, np.int64) % f.ts
        c = np.asarray(dst, np.int64) % f.td
        np.add.at(f.tiles, (b, r, c), 1.0)
        self.fmt = dataclasses.replace(
            self.fmt, tiles=self.fmt.tiles.at[b, r, c].add(1.0))


@register_backend("auto")
class AutoEngine(PallasEngine):
    """``pallas`` with the regime chosen per graph by the autotuner.

    ``prepare`` asks :func:`repro.kernels.autotune.plan_regime` for the
    cheapest execution plan (cost model by default; ``microbench=True``
    times one step of every candidate). Plans are memoized in
    the process-level :data:`~repro.kernels.autotune.PLAN_CACHE` keyed by
    graph *structure*, so ``patch_activity`` / warm re-``prepare`` cycles
    never re-plan, and the compiled solver loop is only rebuilt when the
    plan actually changes.

    Every ``run`` closes the calibration loop: the resolve's measured
    per-step wall time is fed to :mod:`repro.obs.calibrate` as a
    (modeled bytes, measured µs) sample for the plan's regime, so
    model-only planning converges toward this machine's measured
    rankings (``calibrate=False`` opts out). Feeding is independent of
    the obs sinks — it is planner input, not telemetry.
    """

    def __init__(self, *, microbench: bool = False, plan_cache=None,
                 calibrate: bool = True, **kw):
        kw.pop("regime", None)          # the planner owns the regime
        self.microbench = bool(microbench)
        self.calibrate = bool(calibrate)
        self._plan_cache = plan_cache
        self.plan = None
        super().__init__(**kw)

    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        from ..kernels import autotune
        cache = (autotune.PLAN_CACHE if self._plan_cache is None
                 else self._plan_cache)
        plan = autotune.plan_regime(
            graph, microbench=self.microbench, dtype=self.dtype,
            interpret=self.interpret, cache=cache,
            calibration=(None if not self.calibrate else
                         autotune._USE_GLOBAL))
        if plan != self.plan:
            self.plan = plan
            self._apply_plan(plan)
        return super().prepare(graph, activity)

    def run(self, *, tol=None, max_iter=None, s0=None) -> PsiResult:
        t0 = time.perf_counter()
        res = super().run(tol=tol, max_iter=max_iter, s0=s0)
        wall = time.perf_counter() - t0
        it = int(res.iterations)
        # a >3-iteration resolve amortizes compile/dispatch overhead enough
        # for wall/iter to stand in for the step-span time the model predicts
        if (self.calibrate and self.plan is not None and it > 3
                and wall > 0.0 and self.plan.est_bytes > 0.0):
            from ..obs import calibrate as obs_calibrate
            obs_calibrate.get_store().observe(
                self.plan.regime, self.plan.est_bytes, wall / it * 1e6,
                source="step_span")
        return res
    # super().run is already the instrumented PallasEngine.run — marking
    # this thin timer prevents a second nested span/record per resolve
    run._obs_instrumented = True


# --------------------------------------------------------------------- #
# distributed — 2-D block-cyclic shard_map schedule, host-chunked
# --------------------------------------------------------------------- #
@register_backend("distributed")
class DistributedEngine(PsiEngine):
    """Sharded Power-ψ over a (data, model) mesh.

    The device program is a fixed-shape ``chunk_iters``-step scan; the
    criterion is evaluated on the host between chunks (iteration counts are
    therefore multiples of ``chunk_iters``), exactly the
    ``runtime/psi_driver.py`` schedule. The gap norm must be ``l1`` (what the
    sharded step psums). ``s`` is converted to/from node order at the API
    boundary so results interchange with the other backends.

    ``accelerate=True`` applies the Aitken jump at *chunk* granularity via
    :class:`ChunkExtrapolator` (the on-device per-iteration loop would break
    the fixed-shape scan contract). ``patch_edges`` is a block-local O(Δ)
    insert into the node-stable 2-D partition; a genuine block overflow
    (``e_max`` exceeded) is handled per ``on_overflow``:

    * ``"regrow"`` (default) — warn naming the overflowing block and the
      required capacity, rebuild the partitioned device arrays from the
      already-patched host graph at the grown ``e_max``, and return True
      (the patch *succeeded*; callers never see a silent no-op).
    * ``"raise"`` — raise :class:`~repro.core.distributed.BlockOverflowError`
      (block, ``e_max``, required capacity) for callers that budget
      capacity themselves.
    """

    def __init__(self, *, mesh=None, chunk_iters: int = 16,
                 on_overflow: str = "regrow", **kw):
        super().__init__(**kw)
        if self.criterion.norm != "l1":
            raise ValueError("distributed backend psums an l1 gap; "
                             f"got norm={self.criterion.norm!r}")
        if on_overflow not in ("regrow", "raise"):
            raise ValueError(f"on_overflow must be 'regrow' or 'raise'; "
                             f"got {on_overflow!r}")
        self.mesh = mesh
        self.chunk_iters = chunk_iters
        self.on_overflow = on_overflow
        self.dist = None

    def _install_dist(self, dist) -> None:
        self.dist = dist
        self._run_chunk = dist.make_run(chunk_iters=self.chunk_iters)
        self._one_step = jax.jit(dist.make_step())
        self._epi = jax.jit(dist.make_epilogue())

    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        from ..launch.mesh import make_mesh
        from .distributed import DistributedPsi
        self._base_prepare(graph, activity)
        if self.mesh is None:
            self.mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
        self._install_dist(DistributedPsi.from_graph(
            graph, activity, self.mesh, dtype=self.dtype))
        return EngineState(s=self.dist.arrays.c_src)

    def step(self, state: EngineState) -> EngineState:
        s_new, gap = self._one_step(state.s, self.dist.arrays)
        scale = self.criterion.scale(self.host.b_norm)
        return EngineState(s=s_new, gap=scale * float(gap), t=state.t + 1)

    def run(self, *, tol=None, max_iter=None, s0=None) -> PsiResult:
        tol, max_iter = self.criterion.resolve(tol, max_iter)
        part = self.dist.part
        if s0 is None:
            s = self.dist.arrays.c_src
        else:
            s_host = np.asarray(np.asarray(s0),
                                np.dtype(jnp.dtype(self.dtype).name))
            s = jax.device_put(
                part.to_src_layout(s_host),
                jax.sharding.NamedSharding(
                    self.mesh,
                    jax.sharding.PartitionSpec(self.dist.src_axes, None)))
        scale = self.criterion.scale(self.host.b_norm)
        extrap = ChunkExtrapolator(tol) if self.accelerate else None
        it, gap = 0, float("inf")
        while it < max_iter and gap > tol:
            s_new, gap_dev = self._run_chunk(s, self.dist.arrays)
            it += self.chunk_iters
            raw = float(gap_dev)
            gap = scale * raw
            # the host already read this gap — record it, free of syncs
            obs_convergence.record_gap(it, raw=raw, certified=gap)
            s = extrap.advance(s, s_new, gap) if extrap else s_new
        psi_piece = self._epi(s, self.dist.arrays)
        psi = part.from_src_layout(
            np.asarray(psi_piece).reshape(part.d, -1))
        s_node = part.from_src_layout(np.asarray(jax.device_get(s)))
        return self._result(jnp.asarray(psi, self.dtype),
                            jnp.asarray(s_node, self.dtype), gap, it, tol)

    def patch_activity(self, users, lam=None, mu=None) -> bool:
        # partition and edge layouts are untouched; only the activity-derived
        # device arrays are rebuilt (no re-partition, no edge re-sort)
        self.host.patch_activity(users, lam=lam, mu=mu)
        self.ops = self.host.refresh_node_arrays(self.ops, self.dtype)
        self.dist.arrays = self.dist.build_arrays(self.graph, self.activity)
        return True

    def patch_edges(self, src, dst) -> bool:
        """Block-local edge insert into the node-stable 2-D partition.

        The node → (row, col) ownership map depends only on (n, d, mo, q),
        so a new edge lands in exactly one block; it is merged dst-sorted
        into that block's host slice (sentinels stay at the tail) and the
        touched block rows + 1/w entries are scattered into the device
        arrays — no re-partition, no O(M) rebuild. A genuine ``e_max``
        block overflow regrows the partition (with a warning naming the
        block and required capacity) or raises
        :class:`~repro.core.distributed.BlockOverflowError`, per the
        engine's ``on_overflow`` option — never a silent no-op.
        """
        from .distributed import BlockOverflowError, DistributedPsi
        p = self.dist.part
        nc, q = p.nc, p.q
        # probe (no mutation) first: on_overflow='raise' must leave the
        # host mirror untouched, or a caught-and-retried patch would dedup
        # against the half-applied state and silently skip the device insert
        src_k, dst_k = self.host.filter_new_edges(src, dst)
        if src_k.size == 0:
            return True
        s64 = src_k.astype(np.int64)
        d64 = dst_k.astype(np.int64)
        c_of_src = s64 // nc
        off = s64 - c_of_src * nc
        row = off // q
        src_loc = (c_of_src * q + (off - row * q)).astype(np.int32)
        col = d64 // nc
        dst_loc = (d64 - col * nc).astype(np.int32)
        add = np.zeros((p.d, p.mo), np.int64)
        np.add.at(add, (row, col), 1)
        over = p.e_counts + add > p.e_max
        if np.any(over):
            # name the *worst* overflowing block so the reported required
            # capacity belongs to the block in the message
            need = p.e_counts + add
            r_o, c_o = (int(x) for x in
                        np.unravel_index(int(np.argmax(need)), need.shape))
            required = int(need[r_o, c_o])
            if self.on_overflow == "raise":
                raise BlockOverflowError((r_o, c_o), int(p.e_max), required)
            # structured + counted (obs_events_total{event=block_overflow_
            # regrow}) AND still a RuntimeWarning, exactly as before
            obs_log.warn(
                "block_overflow_regrow",
                f"distributed patch_edges: block (row={r_o}, col={c_o}) "
                f"overflows e_max={int(p.e_max)} (insert requires capacity "
                f">= {required}); regrowing the partition from the patched "
                f"graph", category=RuntimeWarning,
                row=r_o, col=c_o, e_max=int(p.e_max), required=required)
            # commit the edges to the host mirror, then repartition once at
            # the grown e_max (one retrace, no second data path)
            self.host.insert_filtered(src_k, dst_k)
            self._graph_stale = True
            self._install_dist(DistributedPsi.from_graph(
                self.graph, self.activity, self.mesh, dtype=self.dtype))
            self.ops = self.host.to_device(self.dtype)
            return True
        self.host.insert_filtered(src_k, dst_k)
        self._graph_stale = True
        a = self.dist.arrays
        new_src_local, new_dst_local = a.src_local, a.dst_local
        for r, c in {(int(r), int(c)) for r, c in zip(row, col)}:
            sel = (row == r) & (col == c)
            s_row = p.src_local[r, c]
            d_row = p.dst_local[r, c]
            cnt = int(p.e_counts[r, c])
            for sl, dl in sorted(zip(src_loc[sel], dst_loc[sel]),
                                 key=lambda e: e[1]):
                ins = int(np.searchsorted(d_row[:cnt], dl, side="right"))
                s_row[ins + 1:cnt + 1] = s_row[ins:cnt].copy()
                d_row[ins + 1:cnt + 1] = d_row[ins:cnt].copy()
                s_row[ins], d_row[ins] = sl, dl
                cnt += 1
            p.e_counts[r, c] = cnt
            new_src_local = new_src_local.at[r, c].set(jnp.asarray(s_row))
            new_dst_local = new_dst_local.at[r, c].set(jnp.asarray(d_row))
        # 1/w changed only at the src endpoints of the new edges
        g = np.unique(s64)
        c_of = g // nc
        off_g = g - c_of * nc
        r_g = off_g // q
        loc_g = c_of * q + (off_g - r_g * q)
        vals = jnp.asarray(self.host.inv_w[g], a.inv_w_src.dtype)
        self.dist.arrays = dataclasses.replace(
            a, src_local=new_src_local, dst_local=new_dst_local,
            inv_w_src=a.inv_w_src.at[r_g, loc_g].set(vals))
        self.ops = self.host.to_device(self.dtype)   # epilogue consistency
        return True


# --------------------------------------------------------------------- #
# async — bounded-staleness overlapped chunk scheduler (repro.asyncexec)
# --------------------------------------------------------------------- #
@register_backend("async")
class AsyncEngine(PsiEngine):
    """Power-ψ through the bounded-staleness chunk scheduler.

    The node set splits into ``num_chunks`` dst-row chunks; each carries an
    epoch counter and steps against the latest published board without a
    global barrier — a chunk may run up to ``tau`` epochs ahead of the
    slowest one (``tau=0`` is exactly the bulk-synchronous schedule).
    Termination is gated by the stale-corrected Eq. 19 certificate and
    always sealed by a synchronous verification sweep, so results are
    interchangeable with every other backend (docs/ASYNC.md).

    ``delay_hook(chunk, epoch) -> seconds`` injects simulated stragglers;
    ``read_hook(reader, neighbor, epochs) -> lag`` forces reads from the
    epoch history (the staleness-injection test harness). The gap norm is
    ``l1`` (what the chunk deltas sum to).
    """

    def __init__(self, *, num_chunks: int = 4, tau: int = 2,
                 max_workers: int | None = None, delay_hook=None,
                 read_hook=None, lane_pad: int = 128, **kw):
        super().__init__(**kw)
        if self.criterion.norm != "l1":
            raise ValueError("async backend sums per-chunk l1 gaps; "
                             f"got norm={self.criterion.norm!r}")
        if self.accelerate:
            raise ValueError(
                "async backend has no Aitken composition (a mixed-epoch Δ "
                "is not a contraction sample — see ChunkExtrapolator's "
                "epoch guard); run accelerate on a synchronous backend")
        from ..asyncexec.staleness import StalenessBound
        StalenessBound(tau)                  # validate tau eagerly
        self.num_chunks = int(num_chunks)
        self.tau = int(tau)
        self.max_workers = max_workers
        self.delay_hook = delay_hook
        self.read_hook = read_hook
        self.lane_pad = int(lane_pad)
        self.sched = None
        self.chunked = None

    def prepare(self, graph: Graph, activity: Activity) -> EngineState:
        from ..asyncexec.scheduler import (AsyncChunkScheduler,
                                           ChunkedOperators)
        from ..asyncexec.staleness import StalenessBound
        self._base_prepare(graph, activity)
        self.chunked = ChunkedOperators(self.host, self.num_chunks,
                                        dtype=self.dtype,
                                        lane_pad=self.lane_pad)
        self.sched = AsyncChunkScheduler(
            self.chunked, bound=StalenessBound(self.tau),
            max_workers=self.max_workers, delay_hook=self.delay_hook,
            read_hook=self.read_hook)
        return EngineState(s=self.chunked.board0)

    def step(self, state: EngineState) -> EngineState:
        """One *synchronous* sweep of every chunk — the protocol-level step
        (the overlap lives in ``run``, not here)."""
        board, raw = self.sched.sync_sweep(jnp.asarray(state.s))
        return EngineState(s=board, gap=float(self._scale()) * raw,
                           t=state.t + 1)

    def run(self, *, tol=None, max_iter=None, s0=None) -> PsiResult:
        tol, max_iter = self.criterion.resolve(tol, max_iter)
        self.sched.reset(s0=None if s0 is None
                         else np.asarray(self._s0_node_order(s0)))
        out = self.sched.run(tol=tol, max_epochs=max_iter,
                             scale=float(self._scale()))
        self.last_run = out                  # staleness/overlap observability
        s_node = jnp.asarray(self.chunked.node_order(out.s), self.dtype)
        t = int(out.epochs.max())
        res = self._result(self.ops.psi_epilogue(s_node), s_node, out.gap,
                           t, tol)
        # converged comes from the scheduler, not gap ≤ tol: an epoch-budget
        # exit reports the latest *stale* gap sum, which may under-report
        # the true residual and must never claim convergence unverified
        return dataclasses.replace(
            res, converged=jnp.asarray(bool(out.converged)),
            # honest currency: chunk-steps / chunks-per-sweep, + epilogue
            matvecs=jnp.asarray(
                -(-out.total_steps // self.num_chunks) + 1, jnp.int32))

    # -- delta hooks (mid-flight capable at the scheduler level) --------- #
    def patch_activity(self, users, lam=None, mu=None) -> bool:
        self.host.patch_activity(users, lam=lam, mu=mu)
        self.ops = self.host.refresh_node_arrays(self.ops, self.dtype)
        self.sched.patch_node_arrays()
        return True

    def patch_edges(self, src, dst) -> bool:
        src, dst = self.host.patch_edges(src, dst)
        self._graph_stale = True
        self.ops = self.host.to_device(self.dtype)
        if src.size:
            self.sched.patch_edges(src, dst)
        return True

    def unpatch_edges(self, src, dst) -> bool:
        src, dst = self.host.remove_edges(src, dst)
        if src.size:
            self._graph_stale = True
            self.ops = self.host.to_device(self.dtype)
            # same touched-chunk rebuild as an insert: the scheduler's
            # patch hook re-reads the (already shrunk) host mirror
            self.sched.patch_edges(src, dst)
        return True
