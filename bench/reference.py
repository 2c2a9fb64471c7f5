"""The plain float64 reference: ψ by power iteration with NumPy and SciPy.

It imports nothing of the program under test and takes nothing the program
has made: it is given the benchmark's own graph and activity.

An edge ``(j → i)`` means j follows i. With ``w_j = Σ_{i followed by j}
(λ_i + μ_i)``, the iteration is ``s ← μ ⊙ Pᵀ(s / w) + μ/(λ+μ)`` from
``s = μ/(λ+μ)``, where ``(Pᵀx)_i = Σ_{j follows i} x_j``, and
``ψ = (λ ⊙ Pᵀ(s / w) + λ/(λ+μ)) / n``. It stops once ``‖Δs‖∞ ≤ 1e-12``.
"""
from __future__ import annotations

import numpy as np

REF_TOL = 1e-12
REF_MAX_ITER = 5_000


class ReferenceStalled(RuntimeError):
    pass


def psi_reference_f64(n, src, dst, lam, mu, *, push=None):
    """``(ψ, iterations)`` in float64; ``push`` reuses a prebuilt
    :func:`push_matrix` of the same edges."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    lam = np.asarray(lam, np.float64)
    mu = np.asarray(mu, np.float64)
    total = lam + mu
    w = np.bincount(src, weights=total[dst], minlength=n)
    inv_w = np.divide(1.0, w, out=np.zeros(n), where=w > 0)
    c = np.divide(mu, total, out=np.zeros(n), where=total > 0)
    d = np.divide(lam, total, out=np.zeros(n), where=total > 0)
    if push is None:
        push = push_matrix(n, src, dst)
    s = c.copy()
    gap = np.inf
    for it in range(1, REF_MAX_ITER + 1):
        s_new = mu * (push @ (s * inv_w)) + c
        gap = float(np.abs(s_new - s).max())
        s = s_new
        if gap <= REF_TOL:
            return (lam * (push @ (s * inv_w)) + d) / n, it
    raise ReferenceStalled(f"float64 reference stalled at ‖Δs‖∞={gap:.3g}")


def push_matrix(n, src, dst):
    """Sparse ``Pᵀ`` with ``(Pᵀx)_i = Σ_{j→i} x_j``, built column-wise by
    follower so that edges sorted by ``src`` need no sort."""
    import scipy.sparse as sp
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    if src.size and np.any(src[1:] < src[:-1]):
        order = np.argsort(src, kind="stable")
        src, dst = src[order], dst[order]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    return sp.csc_matrix((np.ones(src.size), dst, indptr), shape=(n, n))


class Reference:
    """One deployment — graph and activity — and its float64 ψ."""

    def __init__(self, n, src, dst, lam, mu):
        self.n = int(n)
        self.src, self.dst, self.lam, self.mu = src, dst, lam, mu
        self.iterations = 0
        self._push = None

    def psi(self, lam=None, mu=None) -> np.ndarray:
        """ψ of the deployment's activity, or of the graph under another
        ``(lam, mu)``; the push matrix is built once."""
        if self._push is None:
            self._push = push_matrix(self.n, self.src, self.dst)
        psi, self.iterations = psi_reference_f64(
            self.n, self.src, self.dst,
            self.lam if lam is None else lam,
            self.mu if mu is None else mu, push=self._push)
        return psi
