"""Percentile and window arithmetic; latency counts from the due time, so
a stall shows in every request it delays."""

import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import serve
import traffic
from traffic import Record


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        xs = rng.exponential(size=n)
        for q in (0, 50, 90, 95, 100):
            assert traffic.percentile(xs, q) == pytest.approx(
                np.percentile(xs, q), rel=1e-12)
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


def test_latency_from_due_not_from_issue():
    recs = [Record(0, None, 1, due=10.0), Record(1, None, 1, due=10.5),
            Record(2, None, 1, due=11.0)]
    for r in recs:          # the server stalled until 12.0, then answered
        r.issued, r.done = r.due, 12.0
    assert traffic.latencies(recs) == [2.0, 1.5, 1.0]
    assert traffic.completed_in(recs, 11.9) == 0
    assert traffic.completed_in(recs, 12.0) == 3


class _StallingServer:
    """Answers at once, but its first submit blocks for ``stall_s``."""

    def __init__(self, stall_s):
        self.stall_s, self.calls = stall_s, 0

    def submit(self, rec):
        rec.issued = time.perf_counter()
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall_s)
        rec.done = time.perf_counter()


def test_open_loop_stall_delays_later_requests():
    mix = traffic.Mix("m", "open", 4, 1.1, (None,), 0.95, 20.0, None,
                      1.0, {})
    fake = _StallingServer(0.5)
    s = SimpleNamespace(cell=SimpleNamespace(mix=mix),
                        rng=np.random.default_rng(3), submit=fake.submit)
    w = serve.open_loop(s, 20.0, 1.0)
    assert len(w.records) >= 15
    # requests due during the stall were issued late, and their latency
    # from the due time shows it
    late = [r for r in w.records[1:] if r.due < w.records[0].due + 0.4]
    assert late
    for r in late:
        assert r.latency >= (w.records[0].due + 0.5) - r.due - 0.02
    assert max(w.lateness) >= 0.3


def test_closed_loop_clients_wait_for_their_answer():
    mix = traffic.Mix("m", "closed", 4, 1.1, (None,), 0.95, None, 3,
                      1.0, {})
    inflight, peak = [0], [0]
    lock = threading.Lock()

    class Fut:
        def __init__(self, rec):
            self.rec = rec

        def result(self, timeout=None):
            time.sleep(0.02)
            with lock:
                inflight[0] -= 1
            self.rec.done = time.perf_counter()

    def submit(rec):
        rec.issued = time.perf_counter()
        with lock:
            inflight[0] += 1
            peak[0] = max(peak[0], inflight[0])
        return Fut(rec)

    s = SimpleNamespace(cell=SimpleNamespace(mix=mix),
                        rng=np.random.default_rng(4), submit=submit)
    w = serve.closed_loop(s, 3, 0.5)
    assert peak[0] <= 3
    assert 30 <= len(w.records) <= 80
    assert all(r.due <= w.t1 for r in w.records)
