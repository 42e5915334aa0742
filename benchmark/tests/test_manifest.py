"""BENCHMARK.json keeps to the contract's letters, and the harness finds a
cell's files by name."""

import json
import os
import re

import pytest

from benchmark import cells, readers
from benchmark.tests import tiny
from benchmark.tests.contract import WIDTH

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = cells.manifest()
HELD = cells.held_out()          # held to the same letters while they wait


def _both(key):
    return BENCH[key] + HELD[key]


ALL_NAMES = ([("config", c["name"]) for c in _both("configs")]
             + [("workload", w["name"]) for w in _both("workloads")]
             + [("traffic", w["traffic"]) for w in _both("workloads")]
             + [("metric", m["name"])
                for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [("held-out-metric", m["name"])
                for m in HELD["end_to_end"] + HELD["per_layer"]]
             + [("reduced", k) for c in _both("configs") for k in c["reduced"]])
PROVED = sorted(w["name"] for w in BENCH["workloads"])


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json")) < 65536


@pytest.mark.parametrize("kind,name", ALL_NAMES)
def test_names(kind, name):
    assert NAME.match(name), (kind, name)


@pytest.mark.parametrize(
    "metric", _both("end_to_end") + _both("per_layer"),
    ids=lambda m: m["name"] + "@" + ",".join(m.get("workloads", ["all"])))
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if "bound" in metric:      # end to end
        assert 0.01 <= metric["bound"] <= 0.1
        assert set(metric) <= allowed | {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]


def test_names_are_unique_and_lines_short():
    for key in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names))
        assert all(1 <= len(e["why"]) <= 200 and "\t" not in e["why"]
                   for e in BENCH[key])
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", PROVED)
def test_cell_resolves_from_files_by_name(cell):
    c = cells.resolve(cell)
    for spec in (c["config"]["program"], c["config"]["reference"],
                 c["config"]["rows"], c["config"]["flops"],
                 c["config"]["optimizer"]["reference"],
                 c["traffic"]["driver"], c["traffic"]["feature_set"],
                 c["traffic"]["epoch_order"]):
        assert cells.load(spec) is not None, spec
    reported = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert c["per_layer"], "a cell reports at least one per-layer metric"
    for m in c["per_layer"]:
        assert m["moves"] in reported, (m["name"], m["moves"])
        assert callable(cells.load(m["reader"]))
    assert c["limits"], "a cell compares at least one number"


def test_every_file_is_under_paths_and_configs_used():
    used = {w["config"] for w in _both("workloads")}
    assert {w["config"] for w in BENCH["workloads"]} == {
        c["name"] for c in BENCH["configs"]}
    for c in _both("configs"):
        assert c["name"] in used
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert not [k for k in c["reduced"] if WIDTH.search(k)]


def test_a_cell_is_added_without_editing_a_file(tmp_path):
    root = tiny.make_root(tmp_path)       # writes with mode "x" only
    cell = cells.resolve("bert-tiny.tiny-hbm", root)
    assert cell["config"]["hidden_size"] == 64
    assert cell["traffic"]["batch"] == 8
    assert {m["name"] for m in cell["end_to_end"]} == {
        "train_items_per_s_per_chip", "setup_s"}
    assert "train_step_mfu" in {m["name"] for m in cell["per_layer"]}
    # the real cells still resolve there, untouched
    assert cells.resolve("resnet50.fit-hostfed", root)["config"][
        "image_size"] == 224


def test_a_reader_that_finds_nothing_returns_nothing():
    ctx = {"counters": {"setup_end": {}, "window_start": {}, "window_end": {}},
           "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0},
           "peaks": None}
    for name in sorted(os.listdir(os.path.join(cells.HERE, "metrics"))):
        with open(os.path.join(cells.HERE, "metrics", name)) as f:
            assert readers.call(json.load(f), ctx) is None, name


def test_held_out_entries_name_files_that_are_there():
    for c in HELD["configs"]:
        assert os.path.exists(os.path.join(cells.ROOT, c["file"]))
    for w in HELD["workloads"]:
        assert os.path.exists(os.path.join(
            cells.HERE, "traffic", w["traffic"] + ".json"))
        assert w["name"] not in {x["name"] for x in BENCH["workloads"]}
    for m in HELD["end_to_end"] + HELD["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
