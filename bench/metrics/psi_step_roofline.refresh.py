"""psi_step_roofline.refresh: the least time of the traced refreshes'
pushes over the device time of every op inside their ``bench.solve`` spans
(the warm start's upload, gather, kernel, pads, epilogue), in percent.

Each warm re-solve is ``iterations + 1`` pushes: ψ takes one more after the
loop. The least time of a push comes from ``bench/work.py`` and the peaks
table, with N and M of the graph itself.
"""
from bench import work


def read(run):
    if run.trace is None or run.peak is None:
        return None
    device_s = run.trace.device_s_in.get("bench.solve", 0.0)
    if device_s <= 0.0:
        return None
    pushes = sum(r.iterations + 1 for r in run.traced)
    return 100.0 * work.least_seconds(pushes, run.n, run.m, run.peak) \
        / device_s
