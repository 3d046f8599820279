"""JoinServer serving throughput: batched multi-tenant engine vs cold
approx_join driver calls on the same query stream.

Two capacity shape classes are interleaved (the worst case for batching);
the engine must (a) batch same-class queries into fused dispatches and
(b) show ZERO executable-cache compiles after the warmup phase — asserted
here, which makes this bench the compiled-executable-reuse regression gate.

``--distributed`` additionally serves the same workload through the mesh
pipeline at 1/2/4/8 host-platform devices — in BOTH serve modes
(exact-parity gather merge vs psum merge with capacity-planned buckets) —
reporting q/s, measured per-device shuffled bytes, the static per-device
wire-bytes model, dropped-tuple counts, and the per-dataset
Bloom-filter-reuse counter (one build per registered relation across the
whole multi-step run — asserted).  At every mesh size > 1 the psum mode's
wire bytes must be STRICTLY below the gather mode's (asserted: that is the
point of the capacity-planned serve path).  The full row set is written to
``BENCH_serve.json`` so the serving perf trajectory is recorded per run.
Re-execs itself under ``--xla_force_host_platform_device_count=8`` when
needed:

  PYTHONPATH=src python -m benchmarks.serve_bench --distributed

``--kernels`` runs the Pallas-path regression gate instead: batched kernel
serving must beat the retired per-query kernel loop on q/s, bit-identically
per slot of a mixed-seed batch, with zero recompiles/filter rebuilds after
warmup (seeds are runtime kernel operands) — asserted — and writes the
``BENCH_kernel.json`` artifact.

``--async-trace`` runs the async-tier gate: one Poisson arrival trace
(rate = 60% of the warmed engine's calibrated capacity) replayed three
ways — caller-driven step loop, ``AsyncJoinServer`` event loop, 2-replica
``AsyncJoinFrontDoor`` — with per-query bit-parity across all three
asserted, async q/s >= step loop, and async queue-latency p95 STRICTLY
below it.  Writes ``BENCH_async.json``.  ``REPRO_TRACE_QUERIES`` scales
the trace (smoke default 48 in CI, 1024 full; set it to 1_000_000 for a
million-query soak).

``--plans`` runs the query-plan regression gate: a 2-node plan (2-way
stage + fused 3-way stage) served through ``JoinServer.submit_plan`` must
be bit-identical per node to the composed direct ``approx_join`` calls,
beat them on q/s with zero recompiles after warmup and one plan compile
(cache hits after), and the compiled byte model must show the cascaded
Bloom-intersection pushdown strictly reducing modeled shuffle bytes vs a
left-deep binary join tree — all asserted — writing ``BENCH_plan.json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks.common import row, scaled
from repro.core.budget import QueryBudget
from repro.core.cost import SigmaRegistry
from repro.core.join import approx_join
from repro.data.synthetic import overlapping_relations
from repro.runtime.join_serve import JoinRequest, JoinServer

N = scaled(1 << 13, 1 << 11)
SLOTS = 4
ROUNDS = scaled(3, 1)          # main-phase rounds of SLOTS queries per class
MAX_STRATA = 2048
B_MAX = 512
MESH_SIZES = (1, 2, 4, 8)


def _workload(seed: int):
    """Two shape classes (N and 2N rows), one tenant dataset each."""
    return {
        "small": overlapping_relations([N, N], 0.1, seed=seed),
        "large": overlapping_relations([2 * N, 2 * N], 0.1, seed=seed + 1),
    }


def _request(tenant: str, rels, q: int) -> JoinRequest:
    # query ids cycle over the batch width: sigma pipelining defers same-id
    # repeats to later steps, so id diversity is what keeps batches full
    return JoinRequest(rels=rels, budget=QueryBudget(error=0.5),
                       query_id=f"{tenant}/sum{q % SLOTS}", seed=100 + q,
                       max_strata=MAX_STRATA, b_max=B_MAX)


def run() -> list[dict]:
    datasets = _workload(seed=7)
    queries = SLOTS * ROUNDS

    # --- cold driver baseline: one approx_join per query, no reuse --------
    reg = SigmaRegistry()
    t0 = time.perf_counter()
    for q in range(queries):
        for tenant, rels in datasets.items():
            approx_join(rels, QueryBudget(error=0.5), max_strata=MAX_STRATA,
                        b_max=B_MAX, seed=100 + q, sigma_registry=reg,
                        query_id=f"{tenant}/sum")
    cold_s = time.perf_counter() - t0
    cold_n = queries * len(datasets)

    # --- server: warmup covers every (stage, class, batch) executable -----
    server = JoinServer(batch_slots=SLOTS)
    for q in range(SLOTS):
        for tenant, rels in datasets.items():
            server.submit(_request(tenant, rels, q))
    server.run()
    warm = server.diagnostics.snapshot()
    # the timed phase reuses the warmed server: clear the latency rings so
    # the reported percentiles cover ONLY the timed segment (warmup-era
    # waits include compile time and used to leak into the p95)
    server.diagnostics.reset_latencies()

    for q in range(queries):
        for tenant, rels in datasets.items():
            server.submit(_request(tenant, rels, SLOTS + q))
    t0 = time.perf_counter()
    server.run()
    serve_s = time.perf_counter() - t0
    d = server.diagnostics
    recompiles = d.compiles - warm["compiles"]
    assert recompiles == 0, \
        f"executable cache missed after warmup: {recompiles} recompiles"
    assert d.max_batch == SLOTS, d.max_batch

    served = d.queries - warm["queries"]
    snap = d.snapshot()
    return [
        row("serve", mode="cold", queries=cold_n, seconds=round(cold_s, 3),
            qps=round(cold_n / cold_s, 2)),
        row("serve", mode="server", queries=served,
            seconds=round(serve_s, 3), qps=round(served / serve_s, 2),
            compiles=d.compiles, recompiles_after_warmup=recompiles,
            cache_hits=d.cache_hits, max_batch=d.max_batch,
            queue_latency_p50_s=round(snap["queue_latency_p50_s"], 4),
            queue_latency_p95_s=round(snap["queue_latency_p95_s"], 4),
            queue_latency_max_s=round(snap["queue_latency_max_s"], 4)),
        row("serve", mode="speedup",
            x=round((served / serve_s) / (cold_n / cold_s), 2)),
    ]


# -- replayed-trace gate: async event-loop tier vs the caller-driven step
# -- loop on one arrival trace (the ISSUE-6 acceptance bench) ---------------

TRACE_Q = int(os.environ.get("REPRO_TRACE_QUERIES", scaled(1024, 48)))
TRACE_UTIL = 0.6               # arrival rate as a fraction of capacity
EXACT_EVERY = 7                # every 7th trace query is an exact budget


def _trace(queries: int) -> list[tuple]:
    """Deterministic mixed tenant trace: two shape classes interleaved,
    per-tenant query ids cycling the batch width (id diversity keeps sigma
    pipelining from starving batches), a sprinkle of exact budgets.  No
    latency budgets: their sample sizing consults the MEASURED filter time,
    so they are timing-dependent by design and would break the bit-parity
    assertion between replays."""
    trace = []
    tenants = ("small", "large")
    for q in range(queries):
        tenant = tenants[q % 2]
        budget = QueryBudget() if q % EXACT_EVERY == EXACT_EVERY - 1 \
            else QueryBudget(error=0.5)
        trace.append((tenant, dict(
            budget=budget, query_id=f"{tenant}/sum{(q // 2) % SLOTS}",
            seed=100 + q, filter_seed=7, max_strata=MAX_STRATA,
            b_max=B_MAX)))
    return trace


def _warm_for_trace(engine: JoinServer) -> None:
    """Compile every (stage, class, fill-bucket) combination the replay
    can hit: fills of 1/2/4 per tenant, each stage mix (the continuous
    batcher dispatches partial fills, so the pow2 buckets 1 and 2 matter
    as much as the full batch).  Warm ids are disjoint from trace ids, so
    both replays start with identical (empty) trace sigma state."""
    plans = ([("exact", 0)], [("err", 0)],
             [("err", 0), ("exact", 1)],
             [("err", 0), ("err", 1), ("err", 2), ("exact", 3)])
    k = 0
    for tenant in ("small", "large"):
        for plan in plans:
            for kind, j in plan:
                budget = QueryBudget() if kind == "exact" \
                    else QueryBudget(error=0.5)
                engine.submit(JoinRequest(
                    dataset=tenant, budget=budget,
                    query_id=f"{tenant}/warm{j}", seed=900 + k,
                    filter_seed=7, max_strata=MAX_STRATA, b_max=B_MAX))
                k += 1
            engine.run()


def _calibrate_qps(server: JoinServer) -> float:
    """Full-batch capacity of the warmed engine (queries/s); the trace's
    Poisson arrival rate is TRACE_UTIL of this, so the same trace loads
    fast and slow machines equally."""
    n = 0
    t0 = time.perf_counter()
    for r in range(2):
        for q in range(SLOTS):
            for tenant in ("small", "large"):
                server.submit(JoinRequest(
                    dataset=tenant, budget=QueryBudget(error=0.5),
                    query_id=f"{tenant}/cal{q}", seed=500 + SLOTS * r + q,
                    filter_seed=7, max_strata=MAX_STRATA, b_max=B_MAX))
                n += 1
        server.run()
    return n / (time.perf_counter() - t0)


def _replay_step_loop(server: JoinServer, trace: list,
                      arrivals) -> tuple[list, float]:
    """The caller-driven pattern the async tier retires: admit arrivals,
    step only once some shape class can fill a whole batch (or the trace
    is exhausted) — batch width bought with queue-latency budget."""
    from collections import Counter
    results, i = [], 0
    t0 = time.perf_counter()
    while i < len(trace) or server.queue:
        now = time.perf_counter() - t0
        while i < len(trace) and arrivals[i] <= now:
            tenant, kw = trace[i]
            results.append(server.submit(JoinRequest(dataset=tenant, **kw)))
            i += 1
        counts = Counter(r._class for r in server.queue)
        if counts and (i == len(trace)
                       or max(counts.values()) >= SLOTS):
            server.step()
        elif i < len(trace):
            time.sleep(min(max(arrivals[i] - now, 0.0), 0.002))
    return results, time.perf_counter() - t0


def _replay_async(submit, trace: list, arrivals) -> tuple[list, float]:
    """Replay the same arrivals against an async submit(): ingestion
    returns futures immediately; the event loop batches continuously."""
    futs = []
    t0 = time.perf_counter()
    for (tenant, kw), at in zip(trace, arrivals):
        lag = at - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        futs.append(submit(JoinRequest(dataset=tenant, **kw)))
    results = [f.result(timeout=600) for f in futs]
    return results, time.perf_counter() - t0


def _latency_pcts(results: list) -> dict:
    import numpy as np
    queue = np.asarray([r.queue_latency_s for r in results], np.float64)
    e2e = np.asarray([r.e2e_latency_s for r in results], np.float64)
    return {"queue_latency_p50_s": round(float(np.percentile(queue, 50)), 4),
            "queue_latency_p95_s": round(float(np.percentile(queue, 95)), 4),
            "e2e_latency_p95_s": round(float(np.percentile(e2e, 95)), 4)}


def _assert_parity(name: str, base: list, other: list) -> None:
    """Per-trace-index bit-identity across replays: slot results never
    depend on batch composition and per-id sigma sequences are
    order-deterministic, so ANY divergence is a scheduling bug."""
    assert len(base) == len(other)
    for i, (a, b) in enumerate(zip(base, other)):
        ra, rb = a.result, b.result
        assert (float(ra.estimate) == float(rb.estimate)
                and float(ra.error_bound) == float(rb.error_bound)
                and float(ra.count) == float(rb.count)), \
            f"{name}: trace index {i} ({a.query_id}) diverged"


def run_async_trace() -> list[dict]:
    """Replayed-trace gate: the async event-loop tier must serve the SAME
    Poisson arrival trace at q/s >= the step loop with queue-latency p95
    STRICTLY below it, bit-identically per query — all asserted.  A
    2-replica front-door leg (tenant sharding + work stealing) replays the
    trace too, also bit-identically.  Smoke-scaled in CI; set
    REPRO_TRACE_QUERIES for large (e.g. million-query) replays."""
    import numpy as np
    from repro.runtime.async_serve import AsyncJoinFrontDoor, AsyncJoinServer

    datasets = _workload(seed=7)
    trace = _trace(TRACE_Q)

    # --- step-loop baseline ------------------------------------------------
    sync = JoinServer(batch_slots=SLOTS)
    for tenant, rels in datasets.items():
        sync.register_dataset(tenant, rels)
    _warm_for_trace(sync)
    rate = TRACE_UTIL * _calibrate_qps(sync)
    arrivals = np.random.default_rng(11).exponential(
        1.0 / rate, size=len(trace)).cumsum()
    compiles0 = sync.diagnostics.compiles
    sync_res, sync_s = _replay_step_loop(sync, trace, arrivals)
    assert sync.diagnostics.compiles == compiles0, "step loop recompiled"

    # --- async event loop, same engine configuration -----------------------
    with AsyncJoinServer(JoinServer(batch_slots=SLOTS)) as srv:
        for tenant, rels in datasets.items():
            srv.register_dataset(tenant, rels)
        srv.call(lambda: _warm_for_trace(srv.engine)).result()
        compiles0 = srv.snapshot()["compiles"]
        async_res, async_s = _replay_async(srv.submit, trace, arrivals)
        snap = srv.snapshot()
    assert snap["compiles"] == compiles0, "async tier recompiled"
    _assert_parity("async-vs-sync", sync_res, async_res)

    # --- 2-replica front door: tenant sharding + work stealing -------------
    with AsyncJoinFrontDoor(replicas=2, batch_slots=SLOTS) as fd:
        for tenant, rels in datasets.items():
            fd.register_dataset(tenant, rels)
        for rep in fd.replicas:
            rep.call(lambda eng=rep.engine: _warm_for_trace(eng)).result()
        fd_res, fd_s = _replay_async(fd.submit, trace, arrivals)
        steals = fd.steals
    _assert_parity("front-door-vs-sync", sync_res, fd_res)

    sync_p, async_p, fd_p = (_latency_pcts(r)
                             for r in (sync_res, async_res, fd_res))
    sync_qps = len(trace) / sync_s
    async_qps = len(trace) / async_s
    assert async_qps >= sync_qps, \
        f"async tier lost throughput: {async_qps:.2f} < {sync_qps:.2f} q/s"
    assert async_p["queue_latency_p95_s"] < sync_p["queue_latency_p95_s"], \
        (f"async queue p95 not below step loop: {async_p} vs {sync_p}")
    return [
        row("async", mode="step-loop", queries=len(trace),
            seconds=round(sync_s, 3), qps=round(sync_qps, 2), **sync_p),
        row("async", mode="event-loop", queries=len(trace),
            seconds=round(async_s, 3), qps=round(async_qps, 2), **async_p,
            backfilled=snap["backfilled"], recompiles_after_warmup=0),
        row("async", mode="front-door2", queries=len(trace),
            seconds=round(fd_s, 3), qps=round(len(trace) / fd_s, 2),
            **fd_p, steals=steals),
        row("async", mode="speedup",
            x=round(async_qps / sync_qps, 3),
            p95_ratio=round(sync_p["queue_latency_p95_s"]
                            / max(async_p["queue_latency_p95_s"], 1e-9), 2)),
    ]


def run_trace() -> list[dict]:
    """Tracing-overhead gate: the SAME warmed server serves the same query
    stream with tracing off (``NULL_TRACER``) and on (a fresh enabled
    ``Tracer`` per segment), best-of-3 each; tracing must cost < 5% q/s —
    asserted.  The traced segments must also produce a complete artifact:
    a validating Chrome trace export and one byte-reconciliation record
    per served query.  Writes ``BENCH_trace.json``."""
    from repro.runtime.telemetry import (NULL_TRACER, Tracer, chrome_trace,
                                         validate_chrome_trace)

    server = JoinServer(batch_slots=SLOTS)
    for tenant, rels in _workload(seed=7).items():
        server.register_dataset(tenant, rels)

    def submit(q):
        # one filter seed: dataset words build once; ids cycle the batch
        # width so sigma pipelining keeps every segment's batches full
        for tenant in ("small", "large"):
            server.submit(JoinRequest(
                dataset=tenant, budget=QueryBudget(error=0.5),
                query_id=f"{tenant}/sum{q % SLOTS}", seed=100 + q,
                filter_seed=7, max_strata=MAX_STRATA, b_max=B_MAX))

    for q in range(SLOTS):               # warmup: compile every executable
        submit(q)
    server.run()
    warm = server.diagnostics.snapshot()

    queries = SLOTS * max(ROUNDS, 2)     # per-segment width (noise guard)
    segments = 3                         # best-of-3 per mode
    best, tracer = {}, None
    for mode in ("off", "on"):
        best[mode] = float("inf")
        for _seg in range(segments):
            server.tracer = NULL_TRACER if mode == "off" \
                else Tracer(enabled=True)
            server.diagnostics.reset_latencies()
            for q in range(queries):
                submit(SLOTS + q)
            t0 = time.perf_counter()
            server.run()
            best[mode] = min(best[mode], time.perf_counter() - t0)
            if mode == "on":
                tracer = server.tracer
    server.tracer = NULL_TRACER
    d = server.diagnostics
    assert d.compiles == warm["compiles"], "trace segments recompiled"

    served = 2 * queries                 # per segment
    # the traced segment produced the full artifact, not just counters
    n_events = validate_chrome_trace(chrome_trace(tracer))
    assert len(tracer.recon) == served, (len(tracer.recon), served)

    qps_off = served / best["off"]
    qps_on = served / best["on"]
    overhead = qps_on / qps_off
    assert overhead >= 0.95, \
        (f"tracing overhead above 5% q/s: {qps_on:.2f} traced vs "
         f"{qps_off:.2f} untraced")
    return [
        row("trace", mode="off", queries=served,
            seconds=round(best["off"], 3), qps=round(qps_off, 2)),
        row("trace", mode="on", queries=served,
            seconds=round(best["on"], 3), qps=round(qps_on, 2),
            events=n_events, recon_records=len(tracer.recon)),
        row("trace", mode="overhead", x=round(overhead, 3)),
    ]


def run_kernels() -> list[dict]:
    """Batched Pallas serving vs the retired per-query kernel loop.

    The baseline is exactly what ``JoinServer._run_kernel`` used to do: one
    direct ``approx_join(use_kernels=True)`` per query.  The engine must
    (a) beat it on q/s by batching kernel queries through the stacked
    ``(batch_slot, ...)`` grids, (b) show ZERO recompiles and ZERO filter
    rebuilds after warmup across a mixed-seed sweep and mixed batch fills
    (seeds are runtime kernel operands), and (c) stay bit-identical to the
    per-query driver for every slot of a mixed-seed batch — all asserted
    here, making this bench the kernel-path regression gate.
    """
    rels = _workload(seed=7)["small"]
    queries = SLOTS * ROUNDS
    segments = 3                          # best-of-3 (timing noise guard)

    # --- per-query kernel baseline ----------------------------------------
    # two warm calls off the clock: the first compiles the kernel wrappers
    # (pilot round), the second the sigma-fed decide path (t-quantile etc.)
    reg = SigmaRegistry()
    for s in (98, 99):
        approx_join(rels, QueryBudget(error=0.5), max_strata=MAX_STRATA,
                    b_max=B_MAX, seed=s, use_kernels=True,
                    sigma_registry=reg, query_id="warm")
    perq_s = float("inf")
    for seg in range(segments):
        t0 = time.perf_counter()
        for q in range(queries):
            approx_join(rels, QueryBudget(error=0.5), max_strata=MAX_STRATA,
                        b_max=B_MAX, seed=100 + q, use_kernels=True,
                        sigma_registry=reg, query_id=f"k/sum{q % SLOTS}")
        perq_s = min(perq_s, time.perf_counter() - t0)

    # --- batched kernel server --------------------------------------------
    server = JoinServer(batch_slots=SLOTS)
    server.register_dataset("k", rels)

    def submit(q, qid=None):
        # fixed filter_seed + per-query sampling seeds: the dataset words
        # build once, every seed rides the same compiled executables
        return server.submit(JoinRequest(
            dataset="k", budget=QueryBudget(error=0.5),
            query_id=qid or f"k/sum{q % SLOTS}", seed=100 + q, filter_seed=7,
            max_strata=MAX_STRATA, b_max=B_MAX, use_kernels=True))

    for r in range(2):                   # full fills: pilot + sigma rounds
        for q in range(SLOTS):
            submit(8 * r + q)
        server.run()
    submit(0, "odd0"), submit(1, "odd1")  # partial (2-wide) fill
    server.run()
    warm = server.diagnostics.snapshot()

    serve_s, served_seg = float("inf"), 0
    for seg in range(segments):
        # one warmed server serves all three segments: reset the latency
        # rings per segment so no segment's percentiles mix earlier samples
        server.diagnostics.reset_latencies()
        for q in range(queries):
            submit(SLOTS + q)
        for q in range(2):               # mixed fills in the timed phase
            submit(SLOTS + queries + q, f"odd{q}")
        t0 = time.perf_counter()
        server.run()
        dt = time.perf_counter() - t0
        if dt < serve_s:
            serve_s, served_seg = dt, queries + 2
    d = server.diagnostics
    recompiles = d.compiles - warm["compiles"]
    assert recompiles == 0, \
        f"kernel classes recompiled after warmup: {recompiles}"
    assert d.filter_builds == warm["filter_builds"], \
        "seed sweep rebuilt dataset filter words"
    assert d.kernel_gather_bytes == 0.0, d.kernel_gather_bytes
    served = served_seg

    # --- per-slot bit-identity of one mixed-seed batch --------------------
    seeds = (301, 17, 301, 995)
    bq = [server.submit(JoinRequest(
        rels=rels, budget=QueryBudget(error=0.5), query_id=f"bit{i}",
        seed=s, max_strata=MAX_STRATA, b_max=B_MAX, use_kernels=True))
        for i, s in enumerate(seeds)]
    assert server.step() == len(seeds)
    for req, s in zip(bq, seeds):
        direct = approx_join(rels, QueryBudget(error=0.5),
                             max_strata=MAX_STRATA, b_max=B_MAX, seed=s,
                             use_kernels=True)
        assert (float(req.result.estimate) == float(direct.estimate)
                and float(req.result.error_bound)
                == float(direct.error_bound)
                and float(req.result.count) == float(direct.count)), \
            f"slot seed {s} diverged from per-query approx_join"

    perq_qps = queries / perq_s
    serve_qps = served / serve_s
    assert serve_qps > perq_qps, \
        f"batched kernel path lost to per-query: {serve_qps} <= {perq_qps}"
    return [
        row("serve", mode="kernel/per-query", queries=queries,
            seconds=round(perq_s, 3), qps=round(perq_qps, 2)),
        row("serve", mode="kernel/batched", queries=served,
            seconds=round(serve_s, 3), qps=round(serve_qps, 2),
            recompiles_after_warmup=recompiles,
            filter_builds=d.filter_builds,
            kernel_gather_bytes=round(d.kernel_gather_bytes),
            max_batch=d.max_batch),
        row("serve", mode="kernel/speedup",
            x=round(serve_qps / perq_qps, 2)),
    ]


def run_plans() -> list[dict]:
    """Query-plan serving gate: compiled multi-way plans vs composed calls.

    One 2-node plan (a 2-way stage plus a fused 3-way stage referencing it)
    is served two ways over the same id-cycled stream: composed direct
    ``approx_join`` calls per node, and ``JoinServer.submit_plan`` batching
    node queries through the warmed executables.  Asserted: (a) the
    compiled byte model shows the cascaded-intersection pushdown strictly
    reducing modeled shuffle bytes vs the left-deep binary tree on the
    3-way node, (b) one plan compile + cache hits for every resubmission,
    (c) ZERO executable recompiles after warmup, (d) per-node bit-identity
    of a served plan vs the composed direct calls, (e) the batched plan
    path beats the composed driver loop on q/s.
    """
    from repro.core.plan import Plan, PlanNode

    a, b, c = overlapping_relations([N, N, N], 0.1, seed=7)
    server = JoinServer(batch_slots=SLOTS)
    for name, rel in zip("abc", (a, b, c)):
        server.register_dataset(name, [rel])
    plan = Plan((
        PlanNode("ab", ("a", "b"), budget=QueryBudget(error=0.5),
                 max_strata=MAX_STRATA, b_max=B_MAX),
        PlanNode("abc", ("ab", "c"), budget=QueryBudget(error=0.5),
                 max_strata=MAX_STRATA, b_max=B_MAX),
    ))

    # --- pushdown byte model: the point of fusing to one n-way stage ------
    compiled = server.compile_plan(plan)
    m3 = compiled.bytes_model["abc"]
    assert m3["bytes_pushdown"] < m3["bytes_binary"], m3
    assert m3["reduction_x"] > 1.0, m3
    assert compiled.bytes_model["ab"]["reduction_x"] == 1.0  # 2-way: equal

    plans = SLOTS * ROUNDS
    composed = (("ab", [a, b]), ("abc", [a, b, c]))

    # --- composed-driver baseline: one approx_join per node per plan ------
    reg = SigmaRegistry()
    for name, rels in composed:          # warm round off the clock
        approx_join(rels, QueryBudget(error=0.5), max_strata=MAX_STRATA,
                    b_max=B_MAX, seed=90, sigma_registry=reg,
                    query_id=f"warm/{name}")
    t0 = time.perf_counter()
    for q in range(plans):
        for name, rels in composed:
            approx_join(rels, QueryBudget(error=0.5), max_strata=MAX_STRATA,
                        b_max=B_MAX, seed=100 + q, sigma_registry=reg,
                        query_id=f"p{q % SLOTS}/{name}")
    direct_s = time.perf_counter() - t0
    direct_n = plans * len(composed)

    # --- plan server: warmup (pilot + sigma rounds), then the timed phase -
    for r in range(2):
        for q in range(SLOTS):
            server.submit_plan(plan, query_id=f"p{q % SLOTS}",
                               seed=100 + SLOTS * r + q)
        server.run()
    warm = server.diagnostics.snapshot()
    server.diagnostics.reset_latencies()

    for q in range(plans):
        server.submit_plan(plan, query_id=f"p{q % SLOTS}",
                           seed=200 + q)
    t0 = time.perf_counter()
    server.run()
    serve_s = time.perf_counter() - t0
    d = server.diagnostics
    recompiles = d.compiles - warm["compiles"]
    assert recompiles == 0, \
        f"plan stages recompiled after warmup: {recompiles}"
    served = d.queries - warm["queries"]

    # --- per-node bit-identity of one served plan vs the composed calls ---
    handle = server.submit_plan(plan, query_id="bit", seed=993)
    server.run()
    assert handle.done
    for name, rels in composed:
        direct = approx_join(rels, QueryBudget(error=0.5),
                             max_strata=MAX_STRATA, b_max=B_MAX, seed=993,
                             query_id=f"bit/{name}")
        got = handle.results()[name]
        assert (float(got.estimate) == float(direct.estimate)
                and float(got.error_bound) == float(direct.error_bound)
                and float(got.count) == float(direct.count)), \
            f"plan node {name} diverged from the composed direct call"

    # one compile for the plan signature; every resubmission was a cache hit
    assert d.plan_compiles == 1, d.plan_compiles
    assert d.plan_cache_hits == 2 * SLOTS + plans + 1, d.plan_cache_hits

    direct_qps = direct_n / direct_s
    serve_qps = served / serve_s
    assert serve_qps > direct_qps, \
        f"plan serving lost to composed driver: {serve_qps} <= {direct_qps}"
    return [
        row("plan", mode="composed-direct", queries=direct_n,
            seconds=round(direct_s, 3), qps=round(direct_qps, 2)),
        row("plan", mode="server", queries=served,
            seconds=round(serve_s, 3), qps=round(serve_qps, 2),
            recompiles_after_warmup=recompiles,
            plan_compiles=d.plan_compiles,
            plan_cache_hits=d.plan_cache_hits, max_batch=d.max_batch),
        row("plan", mode="pushdown-model", n=m3["n"],
            bytes_pushdown=m3["bytes_pushdown"],
            bytes_binary=m3["bytes_binary"],
            reduction_x=round(m3["reduction_x"], 3),
            overlap=round(m3["overlap"], 4)),
        row("plan", mode="speedup", x=round(serve_qps / direct_qps, 2)),
    ]


def _run_distributed_leg(devices: int,
                         serve_mode: str = "exact-parity") -> dict:
    """Serve one dataset-handle workload on a ``devices``-wide mesh."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch.platform import mesh_devices
    mesh = Mesh(np.array(mesh_devices(devices)), ("data",))
    server = JoinServer(batch_slots=SLOTS, mesh=mesh, serve_mode=serve_mode)
    for tenant, rels in _workload(seed=7).items():
        server.register_dataset(tenant, rels)

    def submit(tenant, q):
        # one seed for the whole run: the per-dataset filter words must be
        # built once per relation and reused every subsequent step; ids
        # cycle so sigma pipelining keeps the batches full
        server.submit(JoinRequest(dataset=tenant,
                                  budget=QueryBudget(error=0.5),
                                  query_id=f"{tenant}/sum{q % SLOTS}",
                                  seed=100, max_strata=MAX_STRATA,
                                  b_max=B_MAX))

    for q in range(SLOTS):               # warmup: compile every executable
        for tenant in ("small", "large"):
            submit(tenant, q)
    server.run()
    warm = server.diagnostics.snapshot()

    queries = SLOTS * ROUNDS
    for q in range(queries):
        for tenant in ("small", "large"):
            submit(tenant, q)
    t0 = time.perf_counter()
    server.run()
    dt = time.perf_counter() - t0
    d = server.diagnostics
    recompiles = d.compiles - warm["compiles"]
    assert recompiles == 0, \
        f"mesh[{devices}] recompiled after warmup: {recompiles}"
    # Bloom-filter reuse: one build per registered relation (2 datasets x 2
    # relations at seed 100) across the whole multi-step run
    assert d.filter_builds == 4, d.filter_builds
    assert d.filter_cache_hits > 0
    served = d.queries - warm["queries"]
    return row("serve", mode=f"mesh{devices}/{serve_mode}", queries=served,
               seconds=round(dt, 3), qps=round(served / dt, 2),
               recompiles_after_warmup=recompiles,
               filter_builds=d.filter_builds,
               filter_cache_hits=d.filter_cache_hits,
               shuffled_bytes_total=round(d.dist_shuffled_tuple_bytes),
               per_device_shuffled_bytes=[
                   int(round(float(b))) for b in d.per_device_shuffled_bytes],
               wire_bytes_model=round(d.dist_wire_bytes_model),
               dropped_tuples=round(d.dist_dropped_tuples),
               per_device_dropped_tuples=[
                   int(round(float(b)))
                   for b in d.per_device_dropped_tuples])


def _all_distributed_legs() -> list[dict]:
    return [_run_distributed_leg(devices, serve_mode)
            for devices in MESH_SIZES
            for serve_mode in ("exact-parity", "psum")]


def _check_psum_beats_gather(rows: list[dict]) -> None:
    """The capacity-planned psum path must put strictly fewer bytes on the
    wire than the gather-merge path at every mesh size > 1, without
    uncounted losses (exact-parity legs may never drop)."""
    by_mode = {r["mode"]: r for r in rows if r["mode"].startswith("mesh")}
    for devices in MESH_SIZES:
        gather = by_mode[f"mesh{devices}/exact-parity"]
        psum = by_mode[f"mesh{devices}/psum"]
        assert gather["dropped_tuples"] == 0, gather
        if devices > 1:
            assert psum["wire_bytes_model"] < gather["wire_bytes_model"], \
                (devices, psum["wire_bytes_model"],
                 gather["wire_bytes_model"])


def run_distributed() -> list[dict]:
    """q/s + shuffle meters at 1/2/4/8 devices, both serve modes.

    On the CPU (``JAX_PLATFORMS=cpu``) without enough host devices, spawns
    a child with ``--xla_force_host_platform_device_count=8`` (the flag must
    precede jax init); the child emits one JSON row per (mesh size, serve
    mode) on stdout.  On an accelerator the legs run in this process, and a
    host with fewer devices than the largest mesh is an error.
    """
    from repro.launch.platform import cpu_device_env
    env = cpu_device_env(max(MESH_SIZES))
    if env is not None:
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.serve_bench",
             "--distributed-child"],
            env=env, capture_output=True, text=True, timeout=3600)
        assert out.returncode == 0, out.stderr[-3000:]
        rows = [json.loads(line) for line in out.stdout.splitlines()
                if line.startswith("{")]
    else:
        rows = _all_distributed_legs()
    _check_psum_beats_gather(rows)
    return rows


def main() -> None:
    from benchmarks.common import print_rows
    if "--distributed-child" in sys.argv:
        for r in _all_distributed_legs():
            print(json.dumps(r), flush=True)
        return
    if "--async-trace" in sys.argv:
        # replayed-trace gate: async tier q/s >= step loop, queue p95
        # strictly below, per-query bit-parity — asserted in
        # run_async_trace; the artifact feeds check_trajectory
        arows = run_async_trace()
        with open("BENCH_async.json", "w") as fh:
            json.dump(arows, fh, indent=1)
        print("wrote BENCH_async.json")
        print_rows(arows)
        return
    if "--plans" in sys.argv:
        # query-plan regression gate: compiled plans must be bit-identical
        # to the composed driver calls, beat them on q/s with zero
        # recompiles, and the cascaded pushdown must strictly reduce
        # modeled shuffle bytes — all asserted in run_plans
        prows = run_plans()
        with open("BENCH_plan.json", "w") as fh:
            json.dump(prows, fh, indent=1)
        print("wrote BENCH_plan.json")
        print_rows(prows)
        return
    if "--trace" in sys.argv:
        # tracing-overhead gate: < 5% q/s vs tracing-off on the same warmed
        # server, with a validating chrome export and per-query recon
        # records — asserted in run_trace; the artifact feeds
        # check_trajectory against the committed trace.json baseline
        trows = run_trace()
        with open("BENCH_trace.json", "w") as fh:
            json.dump(trows, fh, indent=1)
        print("wrote BENCH_trace.json")
        print_rows(trows)
        return
    if "--kernels" in sys.argv:
        # kernel-path regression gate: batched Pallas serving must beat the
        # per-query kernel baseline, bit-identically, with zero recompiles;
        # its own artifact rides beside BENCH_serve.json in CI
        krows = run_kernels()
        with open("BENCH_kernel.json", "w") as fh:
            json.dump(krows, fh, indent=1)
        print("wrote BENCH_kernel.json")
        print_rows(krows)
        return
    rows = run()
    if "--distributed" in sys.argv:
        rows += run_distributed()
        # the artifact that records the serving perf trajectory per run:
        # q/s, per-device shuffled bytes, wire-model bytes, dropped tuples
        with open("BENCH_serve.json", "w") as fh:
            json.dump(rows, fh, indent=1)
        print("wrote BENCH_serve.json")
    print_rows(rows)


if __name__ == "__main__":
    main()
