"""Every configuration, cell and metric that BENCHMARK.json names is a
file of its own that the harness finds by name."""
import json
import os

import pytest

from bench import run

ROOT = run.ROOT
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
CONFIGS = SPEC["configs"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_loads_by_name(name, trace):
    cell = run.load_cell(name, trace)
    assert cell.chips == 1
    assert cell.traffic["mode"] in ("solves", "capped")
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    assert cell.metrics, "every cell reports metrics of both kinds"
    names = {m["name"] for m in cell.metrics}
    if not trace:
        assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_loads_by_name(name):
    assert callable(run.load_reader(name))


@pytest.mark.parametrize("conf", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_config_file_states_source_cut_and_assumptions(conf):
    path = os.path.join(ROOT, conf["file"])
    assert path.startswith(os.path.join(ROOT, "bench") + os.sep)
    cfg = json.load(open(path))
    assert cfg["name"] == conf["name"]
    assert cfg["reduced"] == conf["reduced"]
    for key in cfg["reduced"]:
        assert cfg[key] < cfg["published"][key]
    assert cfg["assumed"] and cfg["source"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        run.load_cell("no-such-cell", False)


def test_per_layer_metrics_name_a_layer_and_what_they_move():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        for cell in m["workloads"]:
            assert cell in CELLS
