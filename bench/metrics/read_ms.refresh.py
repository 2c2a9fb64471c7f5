"""read_ms.refresh: host milliseconds of a refresh's ``bench.read`` phase
(``top_k``, ``rank_of`` and ``scores_batch`` from the fresh ψ, the ranking
cache's copy of ψ to the host and its sort included), mean over the
window."""
import numpy as np


def read(run):
    return 1e3 * float(np.mean([r.phase_s["read"] for r in run.requests]))
