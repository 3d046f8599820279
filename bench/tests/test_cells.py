"""Configurations, traffic mixes and metric readers are files found by the
names in BENCHMARK.json, and the file keeps to the benchmark's format."""

import json
import re
from pathlib import Path

import pytest

import cells
from cells import ROOT, load_cell, load_dataset, load_reader

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert (ROOT / BENCH["command"][1]).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_text():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_per_layer_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(layers) >= 5


def test_configs_and_cells():
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= 1
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["name"] == c["name"] and "guarantees" in cfg
        assert (cells.BENCH / "datasets" / f"{cfg['dataset']}.py").is_file()
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = load_cell(name)
    assert cell.chips in (1, 4)
    assert {m.name for m in cell.end_to_end} >= {"setup_s", "latency_p50_s"}
    assert cell.per_layer
    assert cell.mix.checks and "unanswered" in cell.mix.checks
    for attr in ("generate", "relations", "QUERY", "largest_stratum",
                 "reference", "joined"):
        assert hasattr(cell.dataset, attr), attr
    for m in cell.end_to_end + cell.per_layer:
        assert callable(m.read)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_each_metric_has_a_reader(metric):
    assert (cells.BENCH / "metrics" / f"{metric}.py").is_file()
    assert callable(load_reader(metric))


def test_unknown_cell_refused():
    with pytest.raises(SystemExit):
        load_cell("no_such.cell")


def test_new_metric_needs_only_its_file(tmp_path):
    (tmp_path / "extra_metric.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert load_reader("extra_metric", Path(tmp_path))(None) == 7.0


def test_new_dataset_needs_only_its_file(tmp_path):
    (tmp_path / "extra_data.py").write_text(
        "QUERY = {'agg': 'count', 'expr': 'sum'}\n")
    assert load_dataset("extra_data", Path(tmp_path)).QUERY["agg"] == "count"
    with pytest.raises(FileNotFoundError):
        load_dataset("no_such_data", Path(tmp_path))
