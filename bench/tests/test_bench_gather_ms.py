"""``gather_ms.cold`` on hand-made op lists: the ``psi_gather`` kernel's
device time per loop iteration, silent where no op bears its name."""
import pytest

from bench import harness, tracing, work

N, M = 2_097_152, 32_417_720


def _run(ops, iterations):
    reduced = None if ops is None else tracing.Reduced(
        window_s=1.0, busy_s=1.0, chips=1, device_s_in={"bench.solve": 1.0},
        op_seconds=ops, idle_gaps=[])
    traced = [harness.Request(index=0, timed=True, iterations=iterations)]
    return harness.Run(n=N, m=M, setup_s=1.0, window_s=1.0,
                       requests=traced, traced=traced, trace=reduced,
                       peak=work.peaks("TPU v5 lite"))


def test_gather_ms_counts_each_instance_of_the_kernel_only():
    ops = [("%psi_gather.3 = f32[33706,8,128] custom-call(...)", 4e-3),
           ("%psi_gather = f32[8,8,128] custom-call(...)", 2e-3),
           ("%psi_gather_2.1 = f32[8,8,128] custom-call(...)", 5e-3),
           ("%psi_power_step.6 = (f32[1,8]) custom-call(...)", 3e-3),
           ("%fusion.6 = f32[8] fusion(%psi_gather.3)", 7e-3)]
    read = harness.reader("gather_ms.cold")
    assert read(_run(ops, iterations=2)) == pytest.approx(3.0)


def test_gather_ms_is_silent_without_the_kernel():
    """The XLA gather (the parent's ``fusion``) is no kernel to read."""
    read = harness.reader("gather_ms.cold")
    ops = [("%fusion.6 = f32[34514944] fusion(...)", 8.586),
           ("%psi_power_step.6 = (f32[1,8]) custom-call(...)", 1.426)]
    assert read(_run(ops, iterations=29)) is None
    assert read(_run(None, iterations=29)) is None
    assert read(_run(ops[:1] + [("%psi_gather.1 = f32[8]", 1.0)],
                     iterations=0)) is None
