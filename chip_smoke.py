"""Smoke run of ψ serving on a TPU: the main path once, checked end to end.

    python chip_smoke.py                 # one chip: phases A and B
    python chip_smoke.py --four-chips    # the distributed backend, 4 chips

Phase A serves the paper's largest graph, the Twitter stand-in (465,017
users, 834,797 follows), through ``PsiService`` with the ``reference`` and
``auto`` backends. Each backend makes a cold solve, answers ``top_k`` /
``scores_batch`` / ``rank_of`` queries, then takes an activity patch and an
edge patch, each followed by a warm re-solve. Phase B serves Graph500 RMAT
scale 21 (2,097,152 users, 32.4M follows) through ``auto``: a cold solve,
queries and one activity patch.

``--four-chips`` runs only the ``distributed`` backend on RMAT scale 21 over
meshes (4, 1) and (2, 2), with one edge patch each, and compares it with a
one-chip ``reference`` solve in the same process.

Every solved ψ is compared with a float64 SciPy power iteration on the host
that shares no code with the library: relative L∞ error at most 1e-4 and the
same ordered top-10. The script runs in one process, refuses to run on
anything but a TPU, and prints the JSON line ``{"ok": true, "device": ...}``
last, only after every check has passed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# ‖B‖·‖Δs‖₁ stopping tolerance of every float32 solve (Alg. 2, Eq. 19)
TOL = 1e-3
MAX_ITER = 2_000
# float64 reference: iterate until ‖Δs‖∞ ≤ REF_TOL
REF_TOL = 1e-12
MAX_REL_LINF = 1e-4
TOP = 10
SEED = 1


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# --------------------------------------------------------------------- #
# Independent float64 reference (NumPy/SciPy only)
# --------------------------------------------------------------------- #
def psi_reference_f64(n, src, dst, lam, mu):
    """ψ by plain power iteration in float64.

    An edge (j → i) means j follows i. With w_j = Σ_{i followed by j}
    (λ_i + μ_i), the iteration is s ← μ ⊙ Pᵀ(s / w) + μ/(λ+μ) from
    s = μ/(λ+μ), where (Pᵀx)_i = Σ_{j follows i} x_j, and
    ψ = (λ ⊙ Pᵀ(s / w) + λ/(λ+μ)) / n.
    """
    import scipy.sparse as sp
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    lam = np.asarray(lam, np.float64)
    mu = np.asarray(mu, np.float64)
    total = lam + mu
    w = np.bincount(src, weights=total[dst], minlength=n)
    inv_w = np.divide(1.0, w, out=np.zeros(n), where=w > 0)
    c = np.divide(mu, total, out=np.zeros(n), where=total > 0)
    d = np.divide(lam, total, out=np.zeros(n), where=total > 0)
    push = sp.csr_matrix((np.ones(src.size), (dst, src)), shape=(n, n))
    s = c.copy()
    for it in range(1, MAX_ITER + 1):
        s_new = mu * (push @ (s * inv_w)) + c
        gap = float(np.abs(s_new - s).max())
        s = s_new
        if gap <= REF_TOL:
            return (lam * (push @ (s * inv_w)) + d) / n, it
    raise SmokeFailure(f"float64 reference stalled at ‖Δs‖∞={gap:.3g}")


def top_ids(psi: np.ndarray, k: int) -> np.ndarray:
    return np.argsort(-psi, kind="stable")[:k]


def compare(phase: str, psi, ref) -> float:
    """Relative L∞ error and ordered top-10 of ``psi`` against ``ref``."""
    psi = np.asarray(psi, np.float64)
    check(psi.shape == ref.shape, f"{phase}: ψ shape {psi.shape}")
    check(bool(np.all(np.isfinite(psi))), f"{phase}: non-finite ψ")
    err = float(np.abs(psi - ref).max() / np.abs(ref).max())
    check(err <= MAX_REL_LINF,
          f"{phase}: relative L∞ error {err:.3g} > {MAX_REL_LINF:g}")
    got, want = top_ids(psi, TOP), top_ids(ref, TOP)
    check(np.array_equal(got, want),
          f"{phase}: top-{TOP} {got.tolist()} != reference {want.tolist()}")
    return err


class Platform:
    """One (graph, activity) state and its float64 ψ, patched in step with
    the service under test."""

    def __init__(self, graph, activity):
        self.n = graph.n
        self.src = graph.src.astype(np.int64)
        self.dst = graph.dst.astype(np.int64)
        self.lam = activity.lam.copy()
        self.mu = activity.mu.copy()
        self.refresh()

    def refresh(self) -> np.ndarray:
        t0 = time.perf_counter()
        self.psi, iters = psi_reference_f64(self.n, self.src, self.dst,
                                            self.lam, self.mu)
        log("float64 reference", n=self.n, m=self.src.size,
            iterations=iters, seconds=f"{time.perf_counter() - t0:.2f}")
        return self.psi

    def new_activity(self, rng, count: int):
        users = rng.choice(self.n, count, replace=False)
        lam = rng.uniform(1e-3, 1.0, count)
        mu = rng.uniform(1e-3, 1.0, count)
        self.lam[users], self.mu[users] = lam, mu
        return users, lam, mu

    def new_edges(self, rng, count: int):
        """``count`` follows that are not in the graph and not self-loops."""
        have = self.src * self.n + self.dst
        src = rng.integers(0, self.n, 4 * count)
        dst = rng.integers(0, self.n, 4 * count)
        key = src * self.n + dst
        _, first = np.unique(key, return_index=True)
        keep = np.sort(first)
        keep = keep[(src[keep] != dst[keep]) & ~np.isin(key[keep], have)]
        src, dst = src[keep[:count]], dst[keep[:count]]
        check(src.size == count, "could not draw new edges")
        self.src = np.concatenate([self.src, src])
        self.dst = np.concatenate([self.dst, dst])
        return src.astype(np.int32), dst.astype(np.int32)


# --------------------------------------------------------------------- #
# Phases
# --------------------------------------------------------------------- #
def describe_engine(svc) -> tuple[str, object]:
    eng = svc.engine
    return getattr(eng, "regime", "xla"), getattr(eng, "interpret", None)


def cold_solve(phase: str, svc, ref: np.ndarray) -> None:
    """First solve (compile + run), then the same solve again from c: the
    difference is the compile time."""
    t0 = time.perf_counter()
    svc.resolve()
    res = svc.last_result
    np.asarray(res.psi)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = svc.engine.run(tol=TOL, max_iter=MAX_ITER)
    np.asarray(again.psi)
    solve = time.perf_counter() - t0
    check(bool(res.converged), f"{phase}: cold solve did not converge "
          f"(gap {float(res.gap):.3g} after {int(res.iterations)} iterations)")
    check(int(again.iterations) == int(res.iterations),
          f"{phase}: repeat solve took {int(again.iterations)} iterations, "
          f"first took {int(res.iterations)}")
    err = compare(phase, svc.scores(), ref)
    regime, interp = describe_engine(svc)
    log(phase, step="cold", backend=svc.backend, regime=regime,
        interpret=interp, n=svc.graph.n, m=svc.graph.m,
        iterations=int(res.iterations), gap=f"{float(res.gap):.3e}",
        tol=TOL, converged=bool(res.converged),
        compile_s=f"{first - solve:.3f}", solve_s=f"{solve:.3f}",
        rel_linf=f"{err:.3e}")


def warm_solve(phase: str, step: str, svc, ref: np.ndarray, patch) -> None:
    """Apply ``patch`` (which re-solves warm), then check against the
    reference patched the same way."""
    t0 = time.perf_counter()
    patch()
    res = svc.last_result
    psi = svc.scores()
    wall = time.perf_counter() - t0
    check(bool(res.converged), f"{phase}: {step} re-solve did not converge")
    err = compare(f"{phase} {step}", psi, ref)
    log(phase, step=step, backend=svc.backend, iterations=int(res.iterations),
        gap=f"{float(res.gap):.3e}", converged=bool(res.converged),
        patch_and_solve_s=f"{wall:.3f}", rel_linf=f"{err:.3e}")


def queries(phase: str, svc, ref: np.ndarray, rng) -> None:
    ids, vals = svc.top_k(100)
    check(len(ids) == 100 and bool(np.all(np.diff(vals) <= 0)),
          f"{phase}: top_k(100) not in descending order")
    check(np.array_equal(ids[:TOP], top_ids(ref, TOP)),
          f"{phase}: top_k(100) head differs from the reference top-{TOP}")
    check(np.array_equal(svc.rank_of(ids[:TOP]), np.arange(TOP)),
          f"{phase}: rank_of(top-{TOP}) is not 0..{TOP - 1}")
    scores = svc.scores()
    scale = np.abs(ref).max()
    for _ in range(3):
        users = rng.choice(ref.size, 1_000, replace=False)
        got = svc.scores_batch(users)
        check(np.array_equal(got, scores[users]),
              f"{phase}: scores_batch disagrees with scores()")
        check(float(np.abs(got - ref[users]).max()) <= MAX_REL_LINF * scale,
              f"{phase}: scores_batch off the reference")
        ranks = svc.rank_of(users)
        by_rank = got[np.argsort(ranks)]
        check(bool(np.all(np.diff(by_rank) <= 0)),
              f"{phase}: rank_of ordering disagrees with the scores")
    log(phase, step="queries", top_k=100, scores_batch="3x1000",
        rank_of="3x1000", top10=ids[:TOP].tolist())


def peak_bytes(phase: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(phase, peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                           "not reported"))


def load(phase: str, name: str):
    """A dataset's graph and the paper's heterogeneous activity on it."""
    from repro.core import heterogeneous
    from repro.graphs import load_dataset
    t0 = time.perf_counter()
    graph = load_dataset(name)
    activity = heterogeneous(graph.n, seed=SEED)
    log(phase, graph=name, n=graph.n, m=graph.m,
        build_s=f"{time.perf_counter() - t0:.2f}")
    return graph, activity


def serve(phase: str, graph, activity, backend: str, plat: Platform, *,
          patches: tuple[str, ...]):
    """One float32 PsiService through cold solve, queries and warm
    patches."""
    from repro.core import PsiService
    rng = np.random.default_rng(SEED + 100)
    svc = PsiService(graph, activity, tol=TOL, max_iter=MAX_ITER,
                     backend=backend)
    cold_solve(phase, svc, plat.psi)
    queries(phase, svc, plat.psi, rng)
    for step in patches:
        if step == "activity":
            users, lam, mu = plat.new_activity(rng, 16)
            warm_solve(phase, "update_activity", svc, plat.refresh(),
                       lambda: svc.update_activity(users, lam=lam, mu=mu))
        else:
            src, dst = plat.new_edges(rng, 8)
            warm_solve(phase, "add_edges", svc, plat.refresh(),
                       lambda: svc.add_edges(src, dst))
    return svc


def phase_a() -> None:
    graph, activity = load("A", "twitter")
    for backend in ("reference", "auto"):
        plat = Platform(graph, activity)
        svc = serve(f"A/{backend}", graph, activity, backend, plat,
                    patches=("activity", "edges"))
        if backend == "auto":
            regime, interp = describe_engine(svc)
            check(regime == "edge_tile",
                  f"A/auto: planner chose {regime!r}, expected edge_tile")
            check(interp is False, "A/auto: kernels ran in interpret mode")
            log("A/auto", plan=svc.engine.plan.label())
        del svc
    peak_bytes("A")


def phase_b() -> None:
    graph, activity = load("B", "rmat21")
    plat = Platform(graph, activity)
    svc = serve("B/auto", graph, activity, "auto", plat,
                patches=("activity",))
    regime, interp = describe_engine(svc)
    check(regime == "edge_tile" and interp is False,
          f"B/auto: regime={regime!r} interpret={interp}")
    log("B/auto", plan=svc.engine.plan.label())
    del svc
    peak_bytes("B")


def phase_four_chips() -> None:
    from repro.core import PsiService
    from repro.launch.mesh import make_mesh
    graph, activity = load("D", "rmat21")
    plat = Platform(graph, activity)
    cold_ref = plat.psi
    src, dst = plat.new_edges(np.random.default_rng(SEED + 200), 8)
    patched_ref = plat.refresh()

    def one(phase, backend, engine_opts=None):
        """Cold solve and one edge patch; returns both ψ."""
        svc = PsiService(graph, activity, tol=TOL, max_iter=MAX_ITER,
                         backend=backend, engine_opts=engine_opts)
        cold_solve(phase, svc, cold_ref)
        cold = np.asarray(svc.scores(), np.float64)
        warm_solve(phase, "add_edges", svc, patched_ref,
                   lambda: svc.add_edges(src, dst))
        return cold, np.asarray(svc.scores(), np.float64)

    one_chip = one("D/reference", "reference")
    for shape in ((4, 1), (2, 2)):
        mesh = make_mesh(shape, ("data", "model"))
        phase = f"D/distributed{shape[0]}x{shape[1]}"
        got = one(phase, "distributed", {"mesh": mesh})
        for step, a, b in zip(("cold", "add_edges"), got, one_chip):
            err = compare(f"{phase} {step} vs one chip", a, b)
            log(phase, step=step, rel_linf_vs_one_chip=f"{err:.3e}")
    peak_bytes("D")


# --------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the distributed backend on four chips")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: FAIL: no library at {SRC / 'repro'}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              "device(s))", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: FAIL: needs {want} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__, compile_cache=cache_dir)

    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_a()
            phase_b()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log("done", wall_s=f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
