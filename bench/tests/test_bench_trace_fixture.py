"""The reduction on a trace the chip wrote: one traced solve of
``g500-s21.cold`` on a TPU v5e (``bench/run.py --trace 1 --save-trace``),
cut to its first loop iteration by ``bench/tests/trim_trace.py``."""
import json
from pathlib import Path

import pytest

from bench import harness, tracing, work
from bench.tests.trim_trace import trim

FIXTURE = Path(__file__).resolve().parent / "g500_s21_solve.json"
N, M = 2_097_152, 32_417_720          # the run's graph (its log line)
KERNEL_OP = "%psi_power_step.6 = "
PARENT_KERNEL_OP = "%power_step_call.6 = "


@pytest.fixture
def events():
    return json.loads(FIXTURE.read_text())


def _run(reduced, iterations=1):
    traced = [harness.Request(index=0, timed=True, iterations=iterations)]
    return harness.Run(n=N, m=M, setup_s=1.0, window_s=1.0,
                       requests=traced, traced=traced, trace=reduced,
                       peak=work.peaks("TPU v5 lite"))


def _read(reduced, name):
    return harness.reader(name)(_run(reduced))


def _ops(events):
    return events["devices"]["/device:TPU:0"]


def test_power_step_ms_reads_the_kernel_op(events):
    """The plain sum of the fixture's ``psi_power_step`` op, one loop
    iteration; about the 1.426 s / 29 an earlier chip breakdown of this
    cell gives (the benchmark ledger)."""
    kernel_ms = sum(d for op, _, d in _ops(events)
                    if op.startswith(KERNEL_OP)) * 1e-6
    value = _read(harness.reduce_trace(events), "power_step_ms.cold")
    assert value == pytest.approx(kernel_ms, rel=1e-12)
    assert value == pytest.approx(49.0, rel=0.25)


def test_existing_metrics_read_as_before(events):
    """``iterations.cold``, ``psi_step_roofline.cold`` and
    ``idle_share.cold`` on the fixture, as the reduction read them when it
    was committed."""
    reduced = harness.reduce_trace(events)
    assert reduced.window_s == 0.9218975690000001
    assert reduced.busy_s == 0.907967304
    assert reduced.device_s_in == {"bench.solve": 0.907967304}
    assert _read(reduced, "iterations.cold") == 1.0
    assert _read(reduced, "psi_step_roofline.cold") == \
        pytest.approx(0.04841216319201434, rel=1e-12)
    assert _read(reduced, "idle_share.cold") == \
        pytest.approx(1.5110426004388389, rel=1e-12)


def test_power_step_ms_is_silent_where_the_kernel_is_not_named(events):
    """A program whose kernel bears another name (the one before it was
    named, ``power_step_call``) leaves the metric out and moves none of the
    others."""
    whole = harness.reduce_trace(events)
    for e in _ops(events):
        e[0] = e[0].replace(KERNEL_OP, PARENT_KERNEL_OP)
    renamed = harness.reduce_trace(events)
    assert _read(renamed, "power_step_ms.cold") is None
    for name in ("iterations.cold", "psi_step_roofline.cold",
                 "idle_share.cold"):
        assert _read(renamed, name) == _read(whole, name)


def test_power_step_ms_counts_each_instance_of_the_kernel_only():
    ops = [("%psi_power_step.6 = (f32[1,8]) custom-call(...)", 3e-3),
           ("%psi_power_step = (f32[1,8]) custom-call(...)", 1e-3),
           ("%psi_power_step_2.1 = (f32[1,8]) custom-call(...)", 5e-3),
           ("%fusion.6 = f32[8] fusion(%psi_power_step.6)", 7e-3)]
    reduced = tracing.Reduced(window_s=1.0, busy_s=1.0, chips=1,
                              device_s_in={"bench.solve": 1.0},
                              op_seconds=ops, idle_gaps=[])
    read = harness.reader("power_step_ms.cold")
    assert read(_run(reduced, iterations=2)) == pytest.approx(2.0)
    assert read(_run(None)) is None


def test_trim_keeps_one_iteration_and_closes_the_hole(events):
    assert events["trimmed"]["iterations"] == 29
    ops = _ops(events)
    kernels = [e for e in ops if e[0].startswith(KERNEL_OP)]
    loop, = [e for e in ops if e[0].startswith("%while")]
    assert len(kernels) == 1
    assert loop[1] < kernels[0][1] < loop[1] + loop[2]
    solve, = events["spans"]
    assert solve[1] <= loop[1] and loop[1] + loop[2] <= solve[1] + solve[2]
    assert tracing.reduce(events).idle_share < 0.02


def test_trim_on_a_hand_made_loop():
    """Three iterations of gather then kernel inside a ``while``; what
    follows the last kernel moves back by the two iterations cut."""
    gather, kernel = "%fusion.6 = f32[8]", f"{KERNEL_OP}(f32[1,8])"
    events = {
        "devices": {"/device:TPU:0": [
            ["%while.2 = (f32[1,8])", 10.0, 60.0],
            [gather, 10.0, 15.0], [kernel, 25.0, 5.0],
            [gather, 30.0, 15.0], [kernel, 45.0, 5.0],
            [gather, 50.0, 15.0], [kernel, 65.0, 5.0],
            ["%fusion = f32[8]", 75.0, 8.0]]},
        "spans": [["bench.solve", 0.0, 90.0]],
        "planes": {}}
    cut = trim(events)
    assert cut["trimmed"] == {"iterations": 3, "kept": 1, "removed_ns": 40.0}
    assert cut["devices"]["/device:TPU:0"] == [
        ["%while.2 = (f32[1,8])", 10.0, 20.0],
        [gather, 10.0, 15.0], [kernel, 25.0, 5.0],
        ["%fusion = f32[8]", 35.0, 8.0]]
    assert cut["spans"] == [["bench.solve", 0.0, 50.0]]
