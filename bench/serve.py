"""Drive the program under test through its public serving API.

The server is ``AsyncJoinServer`` over a ``JoinServer`` built from the
cell's configuration; the window's requests go through
``AsyncJoinServer.submit`` and are timed from their due time to the moment
their future resolves.  Nothing here reaches inside the program: it builds
``Relation``s, ``JoinRequest``s and ``QueryBudget``s, reads the results and
the server's diagnostics, and (in a traced run) the engine's ``Tracer``.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import numpy as np

from cells import Cell
from traffic import (Mix, Record, arrival_offsets, id_sequence,
                     request_seeds)

DATASET = "bench"              # the name the dataset is registered under
CLOSED_LOOP_IDS = 4096        # length of the cycled closed-loop id order
WARM_TIMEOUT_S = 900.0


@dataclass
class Served:
    """A built and warmed server with the data it serves."""

    cell: Cell
    tables: tuple                  # the dataset module's tables
    server: object                 # AsyncJoinServer
    tracer: object                 # Tracer or None
    filter_seed: int
    rng: np.random.Generator       # the run's request seeds
    times: dict = field(default_factory=dict)

    @property
    def engine(self):
        return self.server.engine

    def request(self, qid: int, seed: int):
        from repro.core.budget import QueryBudget
        from repro.runtime.join_serve import JoinRequest
        e = self.cell.mix.budget_of(qid)
        budget = QueryBudget() if e is None else QueryBudget(
            error=e, confidence=self.cell.mix.confidence)
        eng = self.cell.config["engine"]
        query = self.cell.dataset.QUERY
        return JoinRequest(
            dataset=DATASET, budget=budget, agg=query["agg"],
            expr=query["expr"],
            query_id=f"q{qid:02d}", seed=int(seed),
            fp_rate=float(eng["fp_rate"]), max_strata=int(eng["max_strata"]),
            b_max=int(eng["b_max"]), use_kernels=bool(eng["use_kernels"]),
            filter_seed=self.filter_seed)

    def submit(self, rec: Record):
        rec.request = self.request(rec.qid, rec.seed)
        rec.issued = time.perf_counter()
        fut = self.server.submit(rec.request)
        fut.add_done_callback(partial(_resolved, rec))
        return fut

    def diagnostics(self) -> dict:
        """The engine's counters, read on its loop between steps."""
        return self.server.call(
            lambda: self.engine.diagnostics.scalars()).result()

    def close(self) -> None:
        self.server.close(drain=False, timeout=30.0)


def _resolved(rec: Record, fut) -> None:
    now = time.perf_counter()
    exc = fut.exception()
    if exc is not None:
        rec.error = repr(exc)
    else:
        rec.done = now


def build(cell: Cell, seed: int, devices: list, trace: bool) -> Served:
    """Generate the dataset's tables from ``seed``, build the server the
    configuration describes and register the tables on it."""
    from repro.core.relation import relation
    from repro.runtime.async_serve import AsyncJoinServer
    from repro.runtime.join_serve import JoinServer
    from repro.runtime.telemetry import Tracer

    cfg, eng, ds = cell.config, cell.config["engine"], cell.dataset
    times = {}
    t0 = time.perf_counter()
    tables = ds.generate(cfg, seed)
    largest = ds.largest_stratum(tables)
    if largest > int(eng["b_max"]):
        raise SystemExit(f"a key has {largest} join rows, over b_max "
                         f"{eng['b_max']}: its stratum's draws would be "
                         "capped")
    times["generate_s"] = time.perf_counter() - t0
    mesh = None
    k = int(eng["mesh_devices"])
    if k > 1:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices[:k]), ("data",))
    tracer = Tracer(capacity=1 << 20) if trace else None
    engine = JoinServer(batch_slots=int(eng["batch_slots"]), mesh=mesh,
                        serve_mode=eng["serve_mode"], tracer=tracer)
    server = AsyncJoinServer(engine=engine)
    t0 = time.perf_counter()
    server.register_dataset(DATASET, [relation(k, v) for k, v in
                                      ds.relations(tables)])
    times["register_s"] = time.perf_counter() - t0
    seeds = np.random.default_rng([int(seed), 1])
    fseed = int(seeds.integers(1, 1 << 31))
    return Served(cell, tables, server, tracer, fseed,
                  np.random.default_rng([int(seed), 2]), times)


def serve_group(s: Served, qids, seeds) -> list:
    """Submit one request per id at once and wait for all of them."""
    recs = [Record(q, s.cell.mix.budget_of(q), int(sd), time.perf_counter())
            for q, sd in zip(qids, seeds)]
    futs = [s.submit(r) for r in recs]
    for f in futs:
        f.result(timeout=WARM_TIMEOUT_S)
    return recs


def warm_up(s: Served) -> None:
    """Compile and load everything the window will run: every id with an
    error budget is served once (its pilot run, in full batches), then
    batches filled to 1, 2 and all slots, of exact and of error-budget
    ids as the mix has them."""
    t0 = time.perf_counter()
    mix, slots = s.cell.mix, s.engine.batch_slots
    rng = np.random.default_rng(0)
    ids = list(range(mix.query_ids))
    error = [q for q in ids if mix.budget_of(q) is not None]
    exact = [q for q in ids if mix.budget_of(q) is None]
    for i in range(0, len(error), slots):
        group = error[i:i + slots]
        serve_group(s, group, request_seeds(len(group), rng))
    for group in (exact, error):
        for fill in sorted({1, 2, slots}):
            if group:
                serve_group(s, group[:fill], request_seeds(fill, rng))
    s.times["warmup_s"] = time.perf_counter() - t0


@dataclass
class Window:
    t0: float
    t1: float
    records: list
    lateness: list            # open loop: submit - due, seconds


def open_loop(s: Served, rate: float, seconds: float) -> Window:
    """Arrivals at their scheduled times, whatever the server does."""
    order = s.cell.mix.schedule_rng()
    offsets = arrival_offsets(rate, seconds, order, s.cell.mix.burst)
    ids = id_sequence(s.cell.mix, len(offsets), order)
    seeds = request_seeds(len(offsets), s.rng)
    t0 = time.perf_counter() + 0.01
    records, lateness = [], []
    for off, q, sd in zip(offsets, ids, seeds):
        due = t0 + float(off)
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec = Record(int(q), s.cell.mix.budget_of(int(q)), int(sd), due)
        s.submit(rec)
        lateness.append(rec.issued - due)
        records.append(rec)
    t1 = t0 + seconds
    wait = t1 - time.perf_counter()
    if wait > 0:
        time.sleep(wait)
    return Window(t0, t1, records, lateness)


def closed_loop(s: Served, clients: int, seconds: float) -> Window:
    """``clients`` callers, each issuing its next request the moment its
    previous result arrives, until the window closes."""
    ids = id_sequence(s.cell.mix, CLOSED_LOOP_IDS, s.cell.mix.schedule_rng())
    seeds = request_seeds(CLOSED_LOOP_IDS, s.rng)
    lock = threading.Lock()
    records: list = []
    t0 = time.perf_counter()
    t1 = t0 + seconds

    def client():
        while True:
            now = time.perf_counter()
            if now >= t1:
                return
            with lock:
                i = len(records) % len(ids)
                q = int(ids[i])
                rec = Record(q, s.cell.mix.budget_of(q), int(seeds[i]), now)
                records.append(rec)
            fut = s.submit(rec)
            try:
                fut.result(timeout=max(t1 + s.cell.mix.drain_s
                                       - time.perf_counter(), 0.0))
            except FutureTimeout:
                return
            except Exception:   # noqa: BLE001 - recorded by _resolved
                continue

    threads = [threading.Thread(target=client, name=f"bench-client{c}",
                                daemon=True) for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + s.cell.mix.drain_s + 5.0)
    return Window(t0, t1, records, [])


def run_window(s: Served, seconds: float, *, loop: Optional[str] = None,
               rate: Optional[float] = None,
               clients: Optional[int] = None) -> Window:
    mix: Mix = s.cell.mix
    loop = loop or mix.loop
    if loop == "open":
        return open_loop(s, float(rate or mix.rate_qps), seconds)
    return closed_loop(s, int(clients or mix.clients), seconds)


def drain(window: Window, limit_s: float) -> None:
    """Wait, up to ``limit_s`` past the window's close, for every request
    due in the window; those still outstanding then never came."""
    deadline = window.t1 + limit_s
    for rec in window.records:
        while rec.done is None and rec.error is None:
            if time.perf_counter() >= deadline:
                return
            time.sleep(0.005)


def answers(window: Window) -> list:
    """Each record's answer as host numbers (None: never came)."""
    out = []
    for rec in window.records:
        if rec.done is None:
            out.append(None)
            continue
        r = rec.request.result
        d = r.diagnostics
        out.append({"estimate": float(r.estimate),
                    "bound": float(r.error_bound),
                    "count": float(r.count),
                    "sampled": bool(d.sampled),
                    "strata_overflow": int(d.strata_overflow)})
    return out
