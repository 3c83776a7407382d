"""``BENCHMARK.json`` against the form it must take, and
every cell's configuration, mix and metric readers found by name."""
import json
import os
import re

import pytest

from bench import generator, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
BENCH = os.path.join(ROOT, SPEC["paths"][0])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_form():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert all(_line(w) for w in SPEC["command"])
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines():
    named = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
             + SPEC["per_layer"])
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert _line(e["why"])
    for c in SPEC["configs"]:
        assert _line(c["source"]) and len(c["reduced"]) <= 16


def test_bounds_and_sources():
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = harness.find_cell(SPEC, cell)
    assert c["chips"] in (1, 4)
    cfg = harness.load_json(BENCH, "configs", f"{c['config']}.json")
    assert cfg["name"] == c["config"]
    mix = harness.load_json(BENCH, "traffic", f"{c['traffic']}.json")
    assert mix["request"] in generator.KINDS
    assert mix.get("devices", 1) <= c["chips"]
    for trace in (False, True):
        for m in harness.cell_metrics(SPEC, cell, trace):
            assert callable(harness.reader(BENCH, m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = harness.cell_metrics(SPEC, cell, True)
    assert layers
    for m in layers:
        assert m["moves"] in e2e


def test_configs_point_at_their_own_files():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert c["file"] == f"{SPEC['paths'][0]}/configs/{c['name']}.json"
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] \
            == c["reduced"]
    used = {c["config"] for c in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


def test_at_most_half_the_cells_take_four_chips():
    four = sum(c["chips"] == 4 for c in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
