"""power_step_calls.cold: the distinct ``psi_power_step`` kernel
instructions in the traced window, which is the kernel calls of one loop
step: a format whose block tables outgrow one call's SMEM splits the step
into several calls, each an instruction of its own.

Instructions are told apart by their names in the op events
(``%psi_power_step.<n> = …``). The reduction keeps the window's ten
costliest ops, so a step of more calls than fit there beside the loop, the
gather and the epilogue would read low. None when no op bears the name.
"""
import re

KERNEL = re.compile(r"^%?(psi_power_step(?:\.\d+)?)\s*=")


def read(run):
    if run.trace is None:
        return None
    calls = {m.group(1) for op, _ in run.trace.op_seconds
             if (m := KERNEL.match(op))}
    return float(len(calls)) if calls else None
