"""A whole run at a tiny scale on the CPU, past the harness's look for a
chip: sound, it is correct; with the timed path broken underneath in each
way a cell can break, ``correct`` comes out false."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

import run
import serve
from conftest import file_cell, tiny_cell
from repro.runtime.join_serve import JoinServer

SEED = (1 << 33) + 7
SECONDS = 2.0


def _cell(name):
    cell = tiny_cell(name)
    cell.mix = dataclasses.replace(cell.mix, drain_s=3.0)
    return cell


def _mixed_cell(**engine):
    """Exact and error budgets in one open loop, with budgets a
    6,000-row join can meet (no cell of BENCHMARK.json has such a mix)."""
    from traffic import Mix
    mix = Mix(name="mixed_open", loop="open", query_ids=8, zipf_s=1.1,
              budgets=(None, 0.1, 0.2), confidence=0.95, rate_qps=8.0,
              clients=None, drain_s=3.0, schedule_seed=1, burst=2,
              checks={"unanswered": 0, "count_mismatch": 0,
                      "exact_rel_err_max": 1e-5, "budget_ratio_max": 1.0,
                      "bound_miss_share": 0.5})
    return file_cell("tpch_sf1_co", mix, **engine)


def _run(cell, cache_log, trace=False):
    devices = jax.devices()[:cell.chips]
    return run.run_cell(cell, SEED, SECONDS, trace, devices, cache_log,
                        time.perf_counter())


def _break_window(monkeypatch, apply):
    """Break the timed path when the window opens (set-up stays sound)."""
    original = serve.run_window

    def broken(*a, **kw):
        apply()
        return original(*a, **kw)
    monkeypatch.setattr(serve, "run_window", broken)


CELLS = ["tpch_sf1_co.exact_closed"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, cache_log):
    cell = _cell(name)
    out = _run(cell, cache_log)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m.name for m in cell.end_to_end}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_layers(name, cache_log):
    cell = _cell(name)
    out = _run(cell, cache_log, trace=True)
    assert out["correct"]
    # every per-layer metric of the cell but the Pallas kernels' (the CPU
    # runs no kernel: the tests run the jnp stages)
    want = {m.name for m in cell.per_layer
            if not m.name.startswith("kernel_ms_per_step.")}
    assert want <= set(out["metrics"]), want - set(out["metrics"])
    assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
    assert out["breakdown"]["device_ops"]


@pytest.mark.parametrize("name", CELLS)
def test_half_the_rows_left_out(name, cache_log, monkeypatch):
    original = JoinServer.register_dataset

    def half(self, dataset, rels):
        lead = rels[0]
        n = lead.capacity // 2
        rels = [lead._replace(valid=lead.valid.at[:n].set(False))] \
            + list(rels[1:])
        return original(self, dataset, rels)
    monkeypatch.setattr(JoinServer, "register_dataset", half)
    out = _run(_cell(name), cache_log)
    assert not out["correct"]
    assert out["checks"]["count_mismatch"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_produced(name, cache_log, monkeypatch):
    original = JoinServer._finish_batch

    def altered(self, batch, **kw):
        original(self, batch, **kw)
        for req in batch:
            req.result = req.result._replace(
                estimate=req.result.estimate * jnp.float32(1.5))
    _break_window(monkeypatch, lambda: monkeypatch.setattr(
        JoinServer, "_finish_batch", altered))
    out = _run(_cell(name), cache_log)
    assert not out["correct"]


def test_step_that_serves_nothing(cache_log, monkeypatch):
    _break_window(monkeypatch, lambda: monkeypatch.setattr(
        JoinServer, "step", lambda self: 0))
    out = _run(_cell("tpch_sf1_co.exact_closed"), cache_log)
    assert not out["correct"]
    assert out["failed"] == out["attempted"] > 0


def test_mesh_sound_then_exchange_left_out(cache_log, monkeypatch):
    # a configuration's mesh_devices puts the server on a mesh (four host
    # devices here; no cell of BENCHMARK.json has one yet)
    cell = _cell("tpch_sf1_co.exact_closed")
    cell.chips = 4
    cell.config["engine"]["mesh_devices"] = 4
    out = _run(cell, cache_log)
    assert out["correct"], out["checks"]
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *a, **kw: x)
    out = _run(cell, cache_log)
    assert not out["correct"]


def test_mixed_budgets_compare_each_kind(cache_log):
    """A mix of exact and error budgets: the exact answers, the error
    answers against their budgets and against their reported bounds are
    each compared, and the bursts arrive together."""
    out = _run(_mixed_cell(), cache_log)
    checks = out["checks"]
    assert set(checks) == {"unanswered", "count_mismatch",
                           "exact_rel_err_max", "budget_ratio_max",
                           "bound_miss_share"}
    assert checks["unanswered"]["value"] == 0
    assert checks["count_mismatch"]["value"] == 0
    assert checks["exact_rel_err_max"]["value"] <= 1e-5
    assert 0.0 <= checks["bound_miss_share"]["value"] <= 1.0


@pytest.mark.parametrize("name", CELLS + ["mixed_open"])
def test_control_fails_where_the_program_passes(name, cache_log):
    """``readings.py`` at a tiny size: the program's exact numbers keep
    to the mix's limits, the control's (bfloat16 exact sums; error-budget
    answers drawn at 68% confidence) break one of them."""
    import readings
    cell = _mixed_cell() if name == "mixed_open" else _cell(name)
    if name == "mixed_open":          # enough answers for a tail
        cell.mix = dataclasses.replace(cell.mix, rate_qps=40.0)
    row = readings.one_seed(cell, SEED, SECONDS, jax.devices()[:1])
    limits = cell.mix.checks
    for k in ("unanswered", "count_mismatch", "exact_rel_err_max"):
        assert row["program"][k] <= limits[k], row
    assert any(v > limits[k] for k, v in row["control"].items()), row
