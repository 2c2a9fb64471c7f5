"""Everything BENCHMARK.json names is found by its name, and the file keeps
to the shape the benchmark's contract gives it."""
import dataclasses
import json
import re

import pytest

from bench import harness, traffic
from bench.harness import BENCH, ROOT, Request, Run

BENCHMARK = harness.load_benchmark()
CELLS = [c["name"] for c in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


def test_names_units_and_keys():
    names = [c["name"] for c in BENCHMARK["configs"]] + CELLS + \
        [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    for kind in (BENCHMARK["configs"], BENCHMARK["workloads"], METRICS):
        assert len({x["name"] for x in kind}) == len(kind)
    for c in BENCHMARK["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCHMARK["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCHMARK["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCHMARK["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_loads_by_name(entry):
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"] == []
    assert cfg["solver"]["dtype"] == "float32"
    assert cfg["assumed"] and cfg["source"]


EXACT = {"cold": "solve_mismatches", "refresh": "read_mismatches"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    entry, cfg, mix, limits = harness.cell_files(BENCHMARK, cell)
    assert entry["config"] == cfg["name"]
    traffic.validate(mix)
    exact = EXACT[traffic.kind(mix)]
    assert set(limits) == {"psi_node_rel", exact}
    assert limits[exact]["limit"] == 0
    psi = limits["psi_node_rel"]
    # set between the program's largest reading and the control's least
    assert psi["lower"] < psi["limit"] < psi["upper"]
    assert psi["upper"] >= 3 * psi["lower"]


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_reader_loads_by_name(metric):
    assert (BENCH / "metrics" / f"{metric['name']}.py").is_file()
    assert callable(harness.reader(metric["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_what_its_layers_move(cell):
    e2e = {m["name"] for m in harness.metrics_for(BENCHMARK, cell, False)}
    layers = harness.metrics_for(BENCHMARK, cell, True)
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    for m in layers:
        assert m["moves"] in e2e, (cell, m["name"])


def _run(trace=None):
    reqs = [Request(index=i, timed=True, t_start=i, t_end=i + lat,
                    iterations=it,
                    phase_s={"patch": 0.01 * lat, "solve": 0.5 * lat,
                             "read": 0.1 * lat})
            for i, (lat, it) in enumerate([(0.9, 12), (1.1, 14), (1.0, 13)])]
    return Run(n=100, m=1000, setup_s=5.0, window_s=3.0,
               requests=reqs, traced=reqs[:1], trace=trace, peak=None)


def test_readers_on_a_synthetic_run():
    run = _run()
    read = {m["name"]: harness.reader(m["name"])(run) for m in METRICS}
    assert read["setup_s"] == 5.0
    assert read["solve_s"] == pytest.approx(1.0)
    assert read["iterations.cold"] == pytest.approx(13.0)
    assert read["refresh_s"] == pytest.approx(1.0)
    assert read["iterations.refresh"] == pytest.approx(13.0)
    assert read["patch_ms.refresh"] == pytest.approx(10.0)
    assert read["read_ms.refresh"] == pytest.approx(100.0)
    # no trace: the trace's metrics are left out, never 0
    for name in ("psi_step_roofline.cold", "idle_share.cold",
                 "psi_step_roofline.refresh", "idle_share.refresh"):
        assert read[name] is None


def test_refresh_trace_readers_on_a_synthetic_trace():
    """The refresh cell's device metrics: the idle share of the traced
    window, and the least time of the traced pushes over the device time
    inside ``bench.solve``."""
    from bench import tracing, work
    reduced = tracing.Reduced(window_s=0.5, busy_s=0.2, chips=1,
                              device_s_in={"bench.solve": 0.1,
                                           "bench.read": 0.05},
                              op_seconds=[], idle_gaps=[])
    run = dataclasses.replace(_run(reduced), peak=work.peaks("TPU v5 lite"))
    idle = harness.reader("idle_share.refresh")(run)
    assert idle == pytest.approx(60.0)
    roof = harness.reader("psi_step_roofline.refresh")(run)
    pushes = run.traced[0].iterations + 1
    assert roof == pytest.approx(
        100 * pushes * (4 * 1000 + 24 * 100) / 819e9 / 0.1)
