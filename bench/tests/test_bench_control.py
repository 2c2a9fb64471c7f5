"""The control — the reference in bfloat16, put in the service's place —
must come out not correct, at a size a test run holds."""
import pytest

from bench.control import ControlService
from bench.tests.tiny import run


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 2**33 + 1])
def test_control_is_not_correct(tmp_path, seed):
    res = run(tmp_path, "g500-s21.cold", seed=seed,
              make_service=ControlService)
    assert not res["correct"]
    psi = res["checks"]["psi_node_rel"]
    assert psi["value"] > psi["limit"]


def test_control_in_float32_is_correct(tmp_path):
    """The same iteration at the configuration's own precision passes, so
    what fails above is the precision and not the stand-in."""
    import jax.numpy as jnp

    def f32(*args):
        return ControlService(*args, dtype=jnp.float32)

    res = run(tmp_path, "g500-s21.cold", make_service=f32)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("seed", [3, 2**31 + 17, 2**33 + 1])
def test_refresh_control_is_not_correct(tmp_path, seed):
    """In a refresh the control patches, re-solves warm from its own
    bfloat16 state and reads from the ψ it serves: its reads agree with
    that ψ, and ψ itself fails."""
    res = run(tmp_path, "g500-s21.refresh", seed=seed,
              make_service=ControlService)
    assert not res["correct"]
    psi = res["checks"]["psi_node_rel"]
    assert psi["value"] > psi["limit"]
    assert res["checks"]["read_mismatches"]["value"] == 0


def test_refresh_control_in_float32_is_correct(tmp_path):
    import jax.numpy as jnp

    def f32(*args):
        return ControlService(*args, dtype=jnp.float32)

    res = run(tmp_path, "g500-s21.refresh", make_service=f32)
    assert res["correct"], res["checks"]


def _deployment():
    import json
    from bench import graphs, harness
    cfg = json.loads((harness.ROOT / "bench/configs/g500-s21.json")
                     .read_text())
    cfg["graph"]["scale"] = 8
    return (cfg, *graphs.build_deployment(cfg, 3))


def test_control_iteration_cap(tmp_path):
    """``--max-iter`` caps each of the control's solves, warm ones too."""
    res = run(tmp_path, "g500-s21.refresh",
              make_service=lambda *a: ControlService(*a, max_iter=3))
    assert not res["correct"]
    svc = ControlService(*_deployment(), max_iter=3)
    svc.resolve()
    assert int(svc.last_result.iterations) == 3
    svc.update_activity([0, 1], [0.5, 0.5], [0.5, 0.5])
    assert int(svc.last_result.iterations) == 3
