"""The trace reduction: interval arithmetic, and a small trace recorded on
a TPU v5e by ``run.py --trace 1`` (``data/``)."""

from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_merge_clip_gaps():
    iv = [(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)]
    assert tr.merge(iv) == [(0, 3), (5, 9)]
    assert tr.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps([(0, 3), (5, 9)], 0, 12) == [(3, 5), (9, 12)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def _fake_trace():
    d0 = tr.DeviceOps("/device:TPU:0", [
        ("fusion.1", 0.0, 2e9, "fusion.1"),
        ("custom-call.3", 1e9, 3e9, "edge_draw.3 [pallas]"),
        ("all-to-all.2", 6e9, 7e9, "all-to-all.2")])
    d1 = tr.DeviceOps("/device:TPU:1", [("fusion.1", 0.0, 1e9, "fusion.1")])
    return tr.Trace([d0, d1], align_ns=0.0)


def test_summary_busy_idle_kernels_collectives():
    spans = [("step", 0.0, 8e9), ("prepare", 0.0, 3e9)]
    s = tr.summarize(_fake_trace(), 0.0, 10e9, spans)
    assert s.window_s == 10.0
    assert s.busy_s == [4.0, 1.0]            # [0,3) + [6,7); [0,1)
    assert s.idle_pct == pytest.approx(75.0)
    assert s.kernel_s(["edge_draw"]) == pytest.approx(1.0)   # 2 s / 2
    assert s.collective_s() == pytest.approx(0.5)
    assert s.idle_gaps == [("step", 3.0), ("no-engine-span", 3.0)]
    assert s.top_ops(1)[0][0] == "prepare/fusion.1"
    assert s.op_s["all-to-all.2"] == pytest.approx(0.5)   # outside a stage


def test_window_clips_ops():
    s = tr.summarize(_fake_trace(), 1e9, 2e9)
    assert s.busy_s == [1.0, 0.0]
    assert s.op_s["fusion.1"] == pytest.approx(0.5)


def test_clock_alignment():
    t = tr.Trace([], align_ns=5e9)
    assert t.to_trace_ns(12.5, 10.0) == pytest.approx(7.5e9)


def test_recorded_chip_trace():
    path = DATA / "exact_closed.xplane.pb"
    trace = tr.load(str(path))
    assert trace.align_ns is not None
    assert trace.devices and trace.devices[0].name == "/device:TPU:0"
    lo = min(s for d in trace.devices for _, s, _, _ in d.ops)
    hi = max(e for d in trace.devices for _, _, e, _ in d.ops)
    s = tr.summarize(trace, lo, hi)
    assert 0 < s.busy_s[0] <= s.window_s
    assert 0 <= s.idle_pct < 100
    # the exact cell's prepare stage runs the Bloom probe as a Pallas kernel
    assert s.kernel_s(["probe_filter_batched"]) > 0
    assert tr._label('%fusion.49 = u32[8]{0} fusion(u32[4]{0} %a)') \
        == "fusion.49"
