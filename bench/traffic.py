"""Traffic generation and the arithmetic over request times.

A traffic mix is a JSON file under ``bench/traffic/`` read by
:func:`Mix.load`.  Its ``budgets`` list gives query id ``i`` the budget
``budgets[i % len(budgets)]``: a relative error budget, or ``null`` for an
exact answer, so one mix may hold both.  An open loop may arrive in
bursts (``burst``: arrivals that share one arrival time, at the same mean
rate).  Every seed gets the same schedule: the multiset of query
ids (Zipf popularity, rounded to whole counts) and, in an open loop, the
multiset of gaps between arrivals (the quantiles of an exponential
distribution at the mix's rate) are fixed by the mix, and so is their
order, drawn once from the mix's ``schedule_seed``.  The run's seed draws
the tables and every request's sampling seed.  With about fifty requests
in a window, the order of arrivals and ids moves the latency quantiles by
tens of percent (at 80% of capacity a request that just misses a step
waits a whole one), so an order drawn per run would measure the order,
not the server.

Latency runs from a request's due time to the moment its result arrives:
in an open loop the due time is the scheduled arrival, so a stall in the
generator or the server delays every later request and shows; in a closed
loop it is the moment the client issued the request.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Optional, Sequence

import numpy as np

SEED_BOUND = 1 << 31     # request seeds are uint32 in the program


@dataclass(frozen=True)
class Mix:
    name: str
    loop: str                      # "open" | "closed"
    query_ids: int
    zipf_s: float
    budgets: tuple                 # by id % len: relative error or None
    confidence: float
    rate_qps: Optional[float]      # open loop
    clients: Optional[int]         # closed loop
    drain_s: float                 # wait for stragglers after the window
    checks: dict                   # number compared -> limit
    schedule_seed: int = 0         # the order of arrivals and ids
    burst: int = 1                 # open loop: arrivals per arrival time

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        return cls(name=Path(path).stem, loop=d["loop"],
                   query_ids=int(d["query_ids"]), zipf_s=float(d["zipf_s"]),
                   budgets=tuple(None if b is None else float(b)
                                 for b in d["budgets"]),
                   confidence=float(d.get("confidence", 0.95)),
                   rate_qps=d.get("rate_qps"), clients=d.get("clients"),
                   drain_s=float(d.get("drain_s", 60.0)),
                   checks=dict(d["checks"]),
                   schedule_seed=int(d["schedule_seed"]),
                   burst=int(d.get("burst", 1)))

    def schedule_rng(self) -> np.random.Generator:
        """The stream that orders this mix's arrivals and ids: the same
        for every run."""
        return np.random.default_rng([self.schedule_seed, 7])

    def budget_of(self, qid: int) -> Optional[float]:
        """Relative error budget of query id ``qid`` (None: exact)."""
        return self.budgets[qid % len(self.budgets)]


def zipf_counts(n: int, ids: int, s: float) -> np.ndarray:
    """Whole counts per id summing to ``n``, proportional to 1/(rank+1)^s
    (largest-remainder rounding, ties to the more popular id)."""
    p = 1.0 / np.arange(1, ids + 1, dtype=np.float64) ** s
    exact = n * p / p.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    order = np.argsort(-(exact - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def id_sequence(mix: Mix, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` query ids: the fixed Zipf multiset, in an order from ``rng``."""
    ids = np.repeat(np.arange(mix.query_ids), zipf_counts(n, mix.query_ids,
                                                          mix.zipf_s))
    return rng.permutation(ids)


def arrival_offsets(rate: float, seconds: float, rng: np.random.Generator,
                    burst: int = 1) -> np.ndarray:
    """Open-loop arrival times in [0, seconds): round(rate * seconds /
    burst) gaps at the exponential distribution's mid-quantiles for
    rate / burst, in an order from ``rng``, and ``burst`` arrivals at each
    time.  The mean rate is the mix's whatever the seed."""
    r = rate / burst
    n = max(int(round(r * seconds)), 1)
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q) / r
    t = np.cumsum(rng.permutation(gaps)) - gaps.min()
    return np.repeat(t[t < seconds], burst)


def request_seeds(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.integers(1, SEED_BOUND, size=n, dtype=np.int64)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile with linear interpolation between order
    statistics (numpy's default rule)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclass
class Record:
    """One request of the window: when it was due, when it was handed to
    the server, and when its result arrived (None while outstanding)."""

    qid: int
    budget: Optional[float]
    seed: int
    due: float
    issued: float = 0.0
    done: Optional[float] = None
    request: object = None
    error: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


def completed_in(records: Sequence[Record], t_end: float) -> int:
    return sum(1 for r in records if r.done is not None and r.done <= t_end)


def latencies(records: Sequence[Record]) -> list:
    return [r.latency for r in records if r.latency is not None]
