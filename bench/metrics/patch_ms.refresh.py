"""patch_ms.refresh: host milliseconds of a refresh's ``bench.patch`` phase
(``PsiService.update_activity(..., resolve=False)``: the host's activity
patch and the upload of the node vectors), mean over the window."""
import numpy as np


def read(run):
    return 1e3 * float(np.mean([r.phase_s["patch"] for r in run.requests]))
