"""gather_ms.cold: device milliseconds of the ``psi_gather`` Pallas kernel
(each edge slot's source value, gathered from a VMEM-resident vector) in
the traced window, which the traced solves' ``bench.solve`` spans fill, per
loop iteration.

The kernel is found by its name: a ``pallas_call``'s name becomes its HLO
instruction's, so its op events read ``%psi_gather.<n> = …``. None when no
op of the breakdown bears that name (a program that gathers in XLA has no
such kernel to read).
"""
import re

KERNEL = re.compile(r"^%?psi_gather(?:\.\d+)?\s*=")


def read(run):
    if run.trace is None:
        return None
    seconds = sum(s for op, s in run.trace.op_seconds if KERNEL.match(op))
    iterations = sum(r.iterations for r in run.traced)
    if seconds <= 0.0 or iterations <= 0:
        return None
    return 1e3 * seconds / iterations
