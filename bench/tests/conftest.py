"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

They run the harness at a tiny scale on the CPU (the command line itself
refuses to run without a TPU) and never time anything."""

from __future__ import annotations

import copy
import os
import sys
from pathlib import Path

# CPU tests: four host devices stand in for a 2x2 mesh; set before JAX
# starts a backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4")

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

TINY = {"scale_factor": 0.004, "engine": {"max_strata": 1024,
                                          "use_kernels": False}}


def shrink(cell, **engine):
    """``cell`` at a scale a CPU test can hold."""
    cell.config = copy.deepcopy(cell.config)
    cell.config["scale_factor"] = TINY["scale_factor"]
    cell.config["engine"].update(TINY["engine"], **engine)
    return cell


def tiny_cell(name: str, **engine):
    """The named cell of BENCHMARK.json at a tiny scale."""
    from cells import load_cell
    return shrink(load_cell(name), **engine)


def file_cell(config: str, mix, chips: int = 1, **engine):
    """A cell that BENCHMARK.json does not list, built from a
    configuration file and ``mix`` (a ``Mix``), at a tiny scale."""
    import json

    from cells import Cell, load_dataset
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    cell = Cell(f"{config}.{mix.name}", chips, cfg,
                load_dataset(cfg["dataset"]), mix, [], [])
    return shrink(cell, **engine)


@pytest.fixture(scope="session")
def cache_log():
    import run
    return run.configure_jax(BENCH / "tests" / ".jax_cache")
