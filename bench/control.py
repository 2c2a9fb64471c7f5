"""The control: the plain reference in bfloat16, put in the service's place.

    python3 bench/control.py --workload g500-s21.cold --seeds 11 12 13 --seconds 1
    python3 bench/control.py --program --workload g500-s21.cold --seeds 11 12 13 --seconds 1
    python3 bench/control.py --workload g500-s21.refresh --max-iter 100 --seeds 11 12 13 --seconds 1

A cell's configuration states float32; the nearest precision below it is
bfloat16, the step that would tempt a later change. The program's own
bfloat16 path (``PsiService(dtype=jnp.bfloat16)``) does not compile for the
TPU (Mosaic wants a 32-bit accumulator in the edge-tile kernel's matmul), so
the control is the reference's own iteration, every array and operation in
bfloat16, run on the device with XLA. It drives the same harness at the
cell's own size and load, one seed after another in one process, and prints
for each seed the numbers ``correct`` compares. A sound benchmark reads the
control as not correct. With ``--program`` it drives the program itself
through the same harness, seed after seed, for the lower reading of each
compared number. The benchmark's own runs never run this.

For a refresh mix the control serves as a thin ``PsiService``: it patches
its own float64 (λ, μ), re-solves warm from its previous s, and answers
``top_k``, ``rank_of`` and ``scores_batch`` with NumPy from the ψ it serves.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class _Result:
    psi: object
    iterations: object
    s: object = None


class _Engine:
    """The reference iteration in one jitted program of ``dtype``."""

    def __init__(self, n, src, dst, lam, mu, tol, max_iter, dtype):
        import jax
        import jax.numpy as jnp
        self.n, self.dtype = int(n), dtype
        order = np.argsort(dst, kind="stable")
        self.src = jnp.asarray(np.asarray(src, np.int32)[order])
        self.dst = jnp.asarray(np.asarray(dst, np.int32)[order])
        self.lam = np.array(lam, np.float64)
        self.mu = np.array(mu, np.float64)
        self.tol, self.max_iter = tol, max_iter
        self._solve = jax.jit(self._program, static_argnums=(0,))

    @staticmethod
    def _program(n, src, dst, lam, mu, s0, warm, tol, max_iter):
        import jax
        import jax.numpy as jnp
        zero = jnp.zeros((), lam.dtype)
        total = lam + mu
        w = jax.ops.segment_sum(total[dst], src, n)
        inv_w = jnp.where(w > 0, 1 / jnp.where(w > 0, w, 1), zero)
        c = jnp.where(total > 0, mu / jnp.where(total > 0, total, 1), zero)
        d = jnp.where(total > 0, lam / jnp.where(total > 0, total, 1), zero)
        b_norm = jnp.max(jax.ops.segment_sum(lam[dst], src, n) * inv_w)

        def push(s):
            return jax.ops.segment_sum((s * inv_w)[src], dst, n,
                                       indices_are_sorted=True)

        def cond(st):
            return (st[1] > tol) & (st[2] < max_iter)

        def body(st):
            s, _, t = st
            s_new = mu * push(s) + c
            return s_new, b_norm * jnp.sum(jnp.abs(s_new - s)), t + 1

        start = jnp.where(warm, s0, c)
        s, _, t = jax.lax.while_loop(
            cond, body, (start, jnp.asarray(jnp.inf, lam.dtype),
                         jnp.asarray(0, jnp.int32)))
        return (lam * push(s) + d) / n, s, t

    def run(self, *, tol=None, max_iter=None, s0=None) -> _Result:
        """A solve from c, or warm from ``s0``."""
        import jax.numpy as jnp
        dt = self.dtype
        warm = s0 is not None
        psi, s, t = self._solve(
            self.n, self.src, self.dst, jnp.asarray(self.lam, dt),
            jnp.asarray(self.mu, dt),
            s0 if warm else jnp.zeros((self.n,), dt), jnp.asarray(warm),
            jnp.asarray(self.tol if tol is None else tol, dt),
            jnp.asarray(self.max_iter if max_iter is None else max_iter,
                        jnp.int32))
        return _Result(psi=psi, iterations=t, s=s)


class ControlService:
    """What the harness drives, with the service's meaning: ``engine.run``
    solves cold from c and returns ψ and the iteration count; the refresh
    path patches, re-solves warm and reads, as ``PsiService`` does."""

    backend = "control-bfloat16"

    def __init__(self, cfg, n, src, dst, lam, mu, dtype=None, max_iter=None):
        import jax.numpy as jnp
        solver = cfg["solver"]
        self.tol = solver["tol"]
        self.max_iter = max_iter or solver["max_iter"]
        self.engine = _Engine(n, src, dst, lam, mu, self.tol, self.max_iter,
                              dtype or jnp.bfloat16)
        self.last_result: _Result | None = None
        self._psi: np.ndarray | None = None
        self._rank: np.ndarray | None = None

    def update_activity(self, users, lam, mu, *, resolve=True) -> None:
        self.engine.lam[users] = lam
        self.engine.mu[users] = mu
        if resolve:
            self.resolve()

    def resolve(self) -> None:
        prev = self.last_result
        self.last_result = self.engine.run(
            s0=None if prev is None else prev.s)
        self._psi = self._rank = None

    def scores(self) -> np.ndarray:
        if self._psi is None:
            self._psi = np.asarray(self.last_result.psi, np.float32)
        return self._psi

    def top_k(self, k):
        psi = self.scores()
        ids = np.argsort(-psi, kind="stable")[:k]
        return ids, psi[ids]

    def rank_of(self, users):
        if self._rank is None:
            order = np.argsort(-self.scores(), kind="stable")
            self._rank = np.empty_like(order)
            self._rank[order] = np.arange(order.size)
        return self._rank[np.asarray(users)]

    def scores_batch(self, users):
        return self.scores()[np.asarray(users)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program", action="store_true",
                    help="drive the program itself instead, for the lower "
                         "reading of each compared number")
    ap.add_argument("--max-iter", type=int,
                    help="cap each of the control's solves at this many "
                         "iterations instead of the config's max_iter: a "
                         "bfloat16 iterate stalls at its rounding floor and "
                         "never meets tol, so at full size every solve "
                         "would run to max_iter")
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + sys.path[1:]
    from bench import harness
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: FAIL: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    def control(*a):
        return ControlService(*a, max_iter=args.max_iter)

    make = harness.program_service if args.program else control
    for seed in args.seeds:
        res = harness.run_cell(
            args.workload, seed=seed, seconds=args.seconds, trace=False,
            t_process=time.perf_counter(), make_service=make,
            require_tpu=args.program)
        print("READING " + json.dumps({"workload": args.workload,
                                       "seed": seed,
                                       "program": args.program,
                                       "max_iter": args.max_iter,
                                       "correct": res["correct"],
                                       "checks": res["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
