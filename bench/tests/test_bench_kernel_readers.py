"""``power_step_calls.cold``, ``gather_roofline.cold`` and
``power_step_roofline.cold`` on the recorded trace of one ``g500-s21.cold``
solve and on hand-made op lists: the kernel calls of a step, each kernel's
share of its roofline, silent where the kernel is not named."""
import json
from pathlib import Path

import pytest

from bench import harness, tracing, work

FIXTURE = Path(__file__).resolve().parent / "g500_s21_solve.json"
N, M = 2_097_152, 32_417_720          # the fixture's graph
KERNEL_OP = "%psi_power_step.6 = "
READERS = ("power_step_calls.cold", "gather_roofline.cold",
           "power_step_roofline.cold")


def _run(reduced, iterations=1, n=N, m=M):
    traced = [harness.Request(index=0, timed=True, iterations=iterations)]
    return harness.Run(n=n, m=m, setup_s=1.0, window_s=1.0,
                       requests=traced, traced=traced, trace=reduced,
                       peak=work.peaks("TPU v5 lite"))


def _reduced(ops):
    return tracing.Reduced(window_s=1.0, busy_s=1.0, chips=1,
                           device_s_in={"bench.solve": 1.0},
                           op_seconds=ops, idle_gaps=[])


@pytest.fixture
def events():
    return json.loads(FIXTURE.read_text())


def test_the_fixture_steps_in_one_call_within_the_roofline(events):
    """The fixture was traced before the gather kernel existed (its gather
    is XLA's ``%fusion.6``), so the gather's share has nothing to read."""
    run = _run(harness.reduce_trace(events))
    read = {name: harness.reader(name)(run) for name in READERS}
    assert read["power_step_calls.cold"] == 1.0
    assert 0.0 < read["power_step_roofline.cold"] < 100.0
    # the least time of one iteration over the kernel's own device time
    kernel_s = sum(d for op, _, d in events["devices"]["/device:TPU:0"]
                   if op.startswith(KERNEL_OP)) * 1e-9
    least = (8 * M + 16 * N) / 819e9
    assert read["power_step_roofline.cold"] == \
        pytest.approx(100 * least / kernel_s, rel=1e-12)
    assert read["gather_roofline.cold"] is None


def test_calls_count_distinct_instructions_of_the_kernel():
    ops = [("%psi_power_step.3 = (f32[1,8]) custom-call(...)", 3e-3),
           ("%psi_power_step.4 = (f32[1,8]) custom-call(...)", 3e-3),
           ("%psi_power_step = (f32[1,8]) custom-call(...)", 1e-3),
           ("%psi_power_step_2.1 = (f32[1,8]) custom-call(...)", 5e-3),
           ("%fusion.6 = f32[8] fusion(%psi_power_step.3)", 7e-3)]
    read = harness.reader("power_step_calls.cold")
    assert read(_run(_reduced(ops))) == 3.0


def test_roofline_shares_on_a_hand_made_split_step():
    """Scale 23's work at the chip's predicted kernel times: 225 ms of
    gather and 195 ms over three power-step calls an iteration."""
    n, m, it = 8_388_608, 131_162_235, 30
    ops = [("%psi_gather.2 = f32[136268,8,128] custom-call(...)", 0.225 * it),
           ("%psi_power_step.3 = (f32[1,8]) custom-call(...)", 0.094 * it),
           ("%psi_power_step.4 = (f32[1,8]) custom-call(...)", 0.094 * it),
           ("%psi_power_step.5 = (f32[1,8]) custom-call(...)", 0.007 * it)]
    run = _run(_reduced(ops), iterations=it, n=n, m=m)
    gather = harness.reader("gather_roofline.cold")(run)
    step = harness.reader("power_step_roofline.cold")(run)
    assert gather == pytest.approx(100 * (8 * m + 4 * n) / 819e9 / 0.225)
    assert step == pytest.approx(100 * (8 * m + 16 * n) / 819e9 / 0.195)
    assert 0.0 < gather < 100.0 and 0.0 < step < 100.0
    assert harness.reader("power_step_calls.cold")(run) == 3.0


@pytest.mark.parametrize("name", READERS)
def test_silent_without_the_kernel(events, name):
    """Renamed kernels, no trace, no iterations or no peaks: None."""
    for e in events["devices"]["/device:TPU:0"]:
        e[0] = e[0].replace("%psi_power_step.", "%power_step_call.") \
            .replace("%psi_gather.", "%gather_call.")
    read = harness.reader(name)
    assert read(_run(harness.reduce_trace(events))) is None
    assert read(_run(None)) is None
    ops = [("%psi_gather.2 = f32[8] custom-call(...)", 1.0),
           ("%psi_power_step.3 = (f32[1,8]) custom-call(...)", 1.0)]
    if name != "power_step_calls.cold":
        assert read(_run(_reduced(ops), iterations=0)) is None
        no_peak = _run(_reduced(ops))
        no_peak.peak = None
        assert read(no_peak) is None
