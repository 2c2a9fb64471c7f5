"""gather_roofline.cold: the ``psi_gather`` kernel's least time over its
device time in the traced window, in percent.

Per loop iteration the gather has to read one int32 source id per edge and
write one float32 value per edge, and read the source vector once, 4 bytes
a user: 8·M + 4·N bytes, with N and M of the graph itself, never of a
padded format. It does no arithmetic, so the bytes bind, at the chip's HBM
bandwidth (``bench/peaks.json`` through ``bench.work.peaks``). None when no
op bears the kernel's name.
"""
import re

KERNEL = re.compile(r"^%?psi_gather(?:\.\d+)?\s*=")


def least_bytes(n: int, m: int) -> int:
    return 8 * m + 4 * n


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = sum(s for op, s in run.trace.op_seconds if KERNEL.match(op))
    iterations = sum(r.iterations for r in run.traced)
    if seconds <= 0.0 or iterations <= 0:
        return None
    least = iterations * least_bytes(run.n, run.m) \
        / run.peak["hbm_bytes_per_s"]
    return 100.0 * least / seconds
