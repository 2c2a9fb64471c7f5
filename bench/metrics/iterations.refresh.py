"""iterations.refresh: PsiResult.iterations per warm re-solve of a refresh,
mean over the window."""
import numpy as np


def read(run):
    return float(np.mean([r.iterations for r in run.requests]))
