"""The engine-phase, event-loop and garbage-collection readers on a
synthetic ``Run``: each gives the mean per ``step`` span of its spans in
ms, and nothing for a program that does not record those spans."""

import pytest

import run
from cells import load_reader

NEW = ("host_ms_per_step.inputs", "host_ms_per_step.decide",
       "host_ms_per_step.finish", "loop_ms_per_step", "gc_ms_per_step")


def _span(name, ts, dur):
    return {"name": name, "cat": "serve", "tid": "replica0", "ts": ts,
            "dur": dur, "args": {"batch": 4}}


def _run(spans):
    return run.Run(None, 10.0, 0.0, 10.0, 1.0, [], {}, {}, spans, None)


def _steps(*extra):
    """Two steps with a parent's spans, plus ``extra``."""
    return [_span("step", 1.0, 1.0), _span("prepare", 1.01, 0.3),
            _span("exact", 1.4, 0.5), _span("linger", 2.5, 0.002),
            _span("step", 3.0, 1.0), _span("prepare", 3.01, 0.3),
            _span("exact", 3.4, 0.5), *extra]


TRACED = _steps(
    _span("inputs", 1.0, 0.010), _span("inputs", 3.0, 0.012),
    _span("decide", 1.31, 0.004), _span("decide", 3.31, 0.006),
    _span("finish", 1.9, 0.060), _span("finish", 3.9, 0.064),
    _span("complete", 2.0, 0.005), _span("complete", 4.0, 0.003),
    _span("drain", 2.01, 0.001), _span("drain", 4.01, 0.001),
    _span("idle", 5.0, 0.5), _span("gc", 1.95, 0.003))


@pytest.mark.parametrize("metric,want", [
    ("host_ms_per_step.inputs", (10 + 12) / 2),
    ("host_ms_per_step.decide", (4 + 6) / 2),
    ("host_ms_per_step.finish", (60 + 64 + 5 + 3) / 2),
    ("loop_ms_per_step", (1 + 1 + 2 + 500) / 2),
    ("gc_ms_per_step", 3 / 2),
])
def test_reader_means_its_spans_per_step(metric, want):
    assert load_reader(metric)(_run(TRACED)) == pytest.approx(want)


def test_window_without_collection_reads_zero():
    spans = [e for e in TRACED if e["name"] != "gc"]
    assert load_reader("gc_ms_per_step")(_run(spans)) == 0.0


@pytest.mark.parametrize("metric", NEW)
def test_program_without_the_spans_reads_nothing(metric):
    """A program that predates the spans (its loop records ``linger``
    only) gives no reading, and no window without steps does."""
    assert load_reader(metric)(_run(_steps())) is None
    assert load_reader(metric)(_run([])) is None
