"""power_step_roofline.cold: the ``psi_power_step`` kernel's least time
over its device time in the traced window (every call of a split step
together), in percent.

Per loop iteration the scatter has to read one gathered float32 value and
one int32 destination per edge, 8·M bytes, and per user read μ, c and s and
write s′, 16·N bytes, with N and M of the graph itself, never of a padded
format; it does one multiply and one add per edge, 2·M FLOP. At the chip's
peaks (``bench/peaks.json`` through ``bench.work.peaks``) the bytes bind:
197 TFLOP/s over 819 GB/s is 240 FLOP a byte, and the step does a quarter
of one. None when no op bears the kernel's name.
"""
import re

KERNEL = re.compile(r"^%?psi_power_step(?:\.\d+)?\s*=")


def least_seconds(n: int, m: int, peak: dict) -> float:
    return max((8 * m + 16 * n) / peak["hbm_bytes_per_s"],
               2 * m / peak["flops_per_s"])


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = sum(s for op, s in run.trace.op_seconds if KERNEL.match(op))
    iterations = sum(r.iterations for r in run.traced)
    if seconds <= 0.0 or iterations <= 0:
        return None
    return 100.0 * iterations * least_seconds(run.n, run.m, run.peak) \
        / seconds
