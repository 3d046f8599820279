"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names the cell; everything else is a file of its own:

* ``configs[].file``: the configuration (data scale, engine settings);
* ``bench/datasets/<dataset>.py``: the tables, query and reference the
  configuration's ``dataset`` names (see ``datasets/tpch_co.py``);
* ``bench/traffic/<traffic>.json``: the traffic mix (see ``traffic.py``);
* ``bench/metrics/<metric>.py``: one reader per metric, end-to-end and
  per-layer alike, each a module with ``read(run) -> float | None``.

A cell or a metric is added by adding files and entries; nothing here
changes.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable

from traffic import Mix

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    dataset: ModuleType
    mix: Mix
    end_to_end: list      # [Metric] the cell reports with --trace 0
    per_layer: list       # [Metric] the cell reports with --trace 1


def _module(kind: str, name: str, directory: Path) -> ModuleType:
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, metrics_dir: Path = BENCH / "metrics") -> Callable:
    return _module("reader", name, metrics_dir).read


def load_dataset(name: str,
                 datasets_dir: Path = BENCH / "datasets") -> ModuleType:
    return _module("dataset", name, datasets_dir)


def _metrics(entries: list, cell: str) -> list:
    return [Metric(m["name"], m["unit"], load_reader(m["name"]))
            for m in entries if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = Mix.load(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config,
                load_dataset(config["dataset"], root / "bench" / "datasets"),
                mix,
                _metrics(bench["end_to_end"], name),
                _metrics(bench["per_layer"], name))
