"""Chip smoke test: TPC-H SF1 customer⋈orders served through JoinServer.

    python chip_smoke.py              # one chip: jnp path, kernel path, async
    python chip_smoke.py --chips 4    # the four-chip mesh phase only

Generates TPC-H at scale factor 1 (150,000 CUSTOMER and 1,500,000 ORDERS
rows, TPC-H specification clause 4.2.5), registers the paper's §5.5 query
SUM(o_totalprice + c_acctbal) over CUSTOMER ⋈ ORDERS and serves it under
exact, error and latency budgets.  Every answer is checked against the exact
answer of ``core/baselines.py`` (itself checked against a float64 host sum):
exact answers within 1e-4 relative, sampled estimates within twice their
reported 95% bound.  The kernel path must lower to Mosaic
(``tpu_custom_call`` in every kernel stage) and is compared with the jnp
path for the same seeds.

Exits non-zero, printing no result line, unless JAX's first device is a
TPU.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Everything runs in this one process: a TPU belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import logging
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SF = 1.0
SEED = 0
SLOTS = 4
MAX_STRATA = 1 << 18
B_MAX = 512
EXACT_RTOL = 1e-4
BOUND_FACTOR = 2.0
# the latency cost model prices the exact join at EXACT_COST_S, so a budget
# of d seconds samples about d / EXACT_COST_S of every stratum: 60%, 30%,
# 10% and 1% below.  At 1% every stratum (SF1 has ~15 orders a customer)
# draws once, and a one-draw stratum reports no variance (ROADMAP §3): a
# query whose bound covers fewer than half its strata is held to
# DEFECT_RTOL of the exact answer instead, and its bound is printed.
# If the process is still alive EXIT_WATCH_S after its result line, the
# Python stacks of every thread go to stderr (runtime shutdown diagnosis).
EXACT_COST_S = 100.0
LATENCY_BUDGETS_S = (60.0, 30.0, 10.0, 1.0)
DEFECT_RTOL = 1e-2
EXIT_WATCH_S = 20

def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX's first device is "
                         f"{devices[0].platform!r}, not a TPU; nothing run")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devices)}")
    return devices


# ---------------------------------------------------------------------------
# data and the exact reference
# ---------------------------------------------------------------------------

def load_tables(scale: float):
    """The query's two relations, its exact answer and join row count."""
    import numpy as np
    from repro.core import baselines
    from repro.data import tpch

    t0 = time.perf_counter()
    t = tpch.generate(scale=scale, seed=SEED)
    rels = tpch.q_customer_orders(t)
    for name, r in zip(("ORDERS", "CUSTOMER"), rels):
        nbytes = sum(x.size * x.dtype.itemsize for x in r)
        log(f"table {name}: rows={r.capacity} bytes={nbytes}")
    log(f"generate_s: {time.perf_counter() - t0:.3f}")
    base = baselines.repartition_join(rels)
    exact, count = float(base.estimate), float(base.count)
    host = float(np.sum(t.orders_totalprice, dtype=np.float64)
                 + np.sum(t.customer_acctbal[t.orders_custkey - 1],
                          dtype=np.float64))
    assert abs(exact - host) <= EXACT_RTOL * abs(host), (exact, host)
    assert count == len(t.orders_key), (count, len(t.orders_key))
    log(f"reference: exact={exact!r} float64_host={host!r} "
        f"join_rows={count:.0f}")
    return rels, exact, count


# ---------------------------------------------------------------------------
# an engine that reports its stage executables
# ---------------------------------------------------------------------------

class CacheLog(logging.Handler):
    """Names the executables JAX's persistent compile cache served (hits),
    compiled (misses) and did not keep (compiled in under
    ``jax_persistent_cache_min_compile_time_secs``), from the records of
    JAX's compiler logger; other records of WARNING and above still go to
    stderr."""

    PATTERNS = (("hit", re.compile(r"cache hit for '([^']+)'")),
                ("miss", re.compile(r"CACHE MISS for '([^']+)'")),
                ("not_kept", re.compile(r"entry for '([^']+)' because it "
                                        r"took <")))

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.names = {kind: [] for kind, _ in self.PATTERNS}
        logger = logging.getLogger("jax._src.compiler")
        logger.setLevel(logging.DEBUG)
        logger.propagate = False
        logger.addHandler(self)

    def emit(self, record):
        msg = record.getMessage()
        for kind, pat in self.PATTERNS:
            m = pat.search(msg)
            if m:
                self.names[kind].append(m.group(1))
                return
        if record.levelno >= logging.WARNING:
            logging.lastResort.handle(record)

    def count(self, kind: str) -> int:
        return len(self.names[kind])


CACHE: CacheLog | None = None


class _AotStage:
    """One engine stage executable, compiled ahead of its first call so the
    compile time, the compiled HLO and the cache outcome can be reported."""

    def __init__(self, log_: dict, label: str, fn):
        self.log, self.label, self.fn, self.compiled = log_, label, fn, None

    def __call__(self, *args):
        if self.compiled is None:
            hits = CACHE.count("hit")
            t0 = time.perf_counter()
            self.compiled = self.fn.lower(*args).compile()
            self.log[self.label] = {
                "compile_s": time.perf_counter() - t0,
                "cache": "hit" if CACHE.count("hit") > hits else "miss",
                "tpu_custom_call": "tpu_custom_call" in
                self.compiled.as_text()}
        return self.compiled(*args)


def smoke_server(**kw):
    """A ``JoinServer`` whose ``stage_log`` records, per stage executable,
    its compile seconds and whether its HLO holds a Mosaic kernel."""
    from repro.runtime.join_serve import JoinServer, ShapeClass

    class SmokeServer(JoinServer):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.stage_log: dict = {}

        def _executable(self, stage, cls, variant, builder):
            kernel = stage.endswith("_k") or (
                isinstance(cls, ShapeClass) and cls.use_kernels)
            label = f"{stage}/{'kernel' if kernel else 'jnp'}" + (
                f"/B{variant}" if isinstance(variant, int)
                else f"/N{cls[0]}")
            return super()._executable(
                stage, cls, variant,
                lambda: _AotStage(self.stage_log, label, builder()))

    return SmokeServer(**kw)


# ---------------------------------------------------------------------------
# queries and checks
# ---------------------------------------------------------------------------

def queries(tag: str, *, latency: bool = True, use_kernels: bool = False):
    """A dozen requests over exact, error and latency budgets (latency
    budgets optional: their draw counts depend on measured time)."""
    from repro.core.budget import QueryBudget
    from repro.runtime.join_serve import JoinRequest

    kinds = {"exact": [QueryBudget()] * 4,
             "error": [QueryBudget(error=e) for e in (.005, .01, .02, .05)]}
    if latency:
        kinds["latency"] = [QueryBudget(latency_s=s)
                            for s in LATENCY_BUDGETS_S]
    reqs = []
    for j in range(4):
        for kind, budgets in kinds.items():
            reqs.append(JoinRequest(
                dataset="tpch", budget=budgets[j], agg="sum", expr="sum",
                query_id=f"{tag}/{kind}{j}", seed=100 + j,
                max_strata=MAX_STRATA, b_max=B_MAX,
                use_kernels=use_kernels))
    return reqs


def check(tag: str, req, exact: float) -> None:
    """Exact answers within EXACT_RTOL; estimates within BOUND_FACTOR of
    their reported bound, or within DEFECT_RTOL when fewer than half the
    sampled strata drew twice (no variance to report).  Prints the
    per-query line."""
    import numpy as np
    r = req.result
    est, bound = float(r.estimate), float(r.error_bound)
    sampled = bool(r.diagnostics.sampled)
    dev = abs(est - exact)
    line = (f"query {tag} {req.query_id}: sampled={sampled} estimate={est!r} "
            f"exact={exact!r} bound={bound!r} rel_err={dev / abs(exact):.3e}")
    assert int(r.diagnostics.strata_overflow) == 0, req.query_id
    if not sampled:
        log(line)
        assert dev <= EXACT_RTOL * abs(exact), (req.query_id, est, exact)
        return
    n = np.asarray(r.stats.n_sampled)[np.asarray(r.stats.valid)]
    drawn = n[n > 0]
    twice = float(np.mean(drawn >= 2))
    log(f"{line} draws={int(drawn.sum())} strata={drawn.size} "
        f"share_drawn_twice={twice:.3f}")
    if twice < 0.5:
        log(f"query {tag} {req.query_id}: bound covers {twice:.1%} of the "
            f"strata (one-draw strata report no variance, ROADMAP §3); "
            f"held to rel_err <= {DEFECT_RTOL}")
        assert dev <= DEFECT_RTOL * abs(exact), (req.query_id, est, exact)
    else:
        assert bound > 0 and dev <= BOUND_FACTOR * bound, \
            (req.query_id, est, exact, bound)


def serve(server, reqs) -> float:
    """Submit and serve ``reqs``; returns the serving seconds."""
    for r in reqs:
        server.submit(r)
    t0 = time.perf_counter()
    server.run()
    dt = time.perf_counter() - t0
    assert all(r.done for r in reqs)
    return dt


def identical(a, b) -> bool:
    import numpy as np
    fields = [(a.estimate, b.estimate), (a.error_bound, b.error_bound),
              (a.count, b.count)]
    if a.stats is not None and b.stats is not None:
        fields += list(zip(a.stats, b.stats))
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in fields)


def report_stages(tag: str, server) -> dict:
    for label, info in sorted(server.stage_log.items()):
        log(f"stage {tag} {label}: compile_s={info['compile_s']:.3f} "
            f"cache={info['cache']} "
            f"tpu_custom_call={info['tpu_custom_call']}")
    return server.stage_log


def peak_bytes(device) -> None:
    stats = device.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def run_one_chip(scale: float = SF) -> dict:
    """jnp path, kernel path, then the async tier, on the default device.
    Returns the kernel server's stage log for the caller's Mosaic check."""
    import jax
    from repro.core.cost import CostModel
    from repro.runtime.async_serve import AsyncJoinServer

    rels, exact, join_rows = load_tables(scale)
    cost = CostModel(beta_compute=EXACT_COST_S / join_rows, epsilon=1e-3)

    jnp_srv = smoke_server(batch_slots=SLOTS, cost_model=cost)
    jnp_srv.register_dataset("tpch", rels)
    jnp_reqs = queries("jnp")
    dt = serve(jnp_srv, jnp_reqs)
    log(f"served jnp: {len(jnp_reqs)} queries, {jnp_srv.diagnostics.steps} "
        f"steps, {dt:.3f}s wall (compiles included)")
    for r in jnp_reqs:
        check("jnp", r, exact)
    report_stages("jnp", jnp_srv)

    k_srv = smoke_server(batch_slots=SLOTS, cost_model=cost)
    k_srv.register_dataset("tpch", rels)
    k_reqs = queries("kernel", use_kernels=True)
    dt = serve(k_srv, k_reqs)
    log(f"served kernel: {len(k_reqs)} queries, {k_srv.diagnostics.steps} "
        f"steps, {dt:.3f}s wall (compiles included)")
    for r in k_reqs:
        check("kernel", r, exact)
    stage_log = report_stages("kernel", k_srv)

    # kernel vs jnp for the same seeds; latency budgets size their draws
    # from measured time, so only exact and error budgets compare
    same = []
    for a, b in zip(jnp_reqs, k_reqs):
        if "latency" in a.query_id:
            continue
        bit = identical(a.result, b.result)
        same.append(bit)
        if a.result.stats is not None:
            assert (jax.device_get(a.result.stats.n_sampled)
                    == jax.device_get(b.result.stats.n_sampled)).all(), \
                a.query_id
        log(f"kernel_vs_jnp {a.query_id.split('/')[1]}: "
            f"bit_identical={bit} jnp={float(a.result.estimate)!r} "
            f"kernel={float(b.result.estimate)!r}")
    log(f"kernel_vs_jnp_all_bit_identical: {all(same)}")

    a_reqs = queries("async", latency=False)[:4]
    with AsyncJoinServer(engine=jnp_srv) as srv:
        futs = [srv.submit(r) for r in a_reqs]
        done = [f.result(timeout=600) for f in futs]
    for r in done:
        check("async", r, exact)
    log(f"served async: {len(done)} queries through one replica")
    peak_bytes(jax.devices()[0])
    return stage_log


def run_four_chips(scale: float = SF) -> None:
    """Exact-parity and psum over a 4-device mesh, against the single-chip
    server on device 0 and the exact reference."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.runtime.join_serve import JoinServer

    rels, exact, _ = load_tables(scale)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    single = JoinServer(batch_slots=SLOTS)
    single.register_dataset("tpch", rels)
    s_reqs = queries("single", latency=False)[:SLOTS]   # one full step
    dt = serve(single, s_reqs)
    log(f"single: {len(s_reqs)} queries in {dt:.3f}s wall "
        "(compiles included)")
    for r in s_reqs:
        check("single", r, exact)
    for mode in ("exact-parity", "psum"):
        srv = JoinServer(batch_slots=SLOTS, mesh=mesh, serve_mode=mode)
        srv.register_dataset("tpch", rels)
        reqs = queries(mode, latency=False)[:SLOTS]
        dt = serve(srv, reqs)
        for r in reqs:
            check(mode, r, exact)
        same = [identical(a.result, b.result)
                for a, b in zip(s_reqs, reqs)]
        d = srv.diagnostics
        per_dev = [float(x) for x in d.per_device_shuffled_bytes]
        drops = [float(x) for x in d.per_device_dropped_tuples]
        log(f"mesh4 {mode}: {len(reqs)} queries in {dt:.3f}s wall "
            f"(compiles included); bit_identical_to_single={all(same)}")
        log(f"mesh4 {mode}: per_device_shuffled_bytes={per_dev} "
            f"dist_dropped_tuples={d.dist_dropped_tuples!r} "
            f"per_device_dropped_tuples={drops}")
        assert all(b > 0 for b in per_dev), (mode, per_dev)
        if mode == "exact-parity":
            assert d.dist_dropped_tuples == 0, d.dist_dropped_tuples
    for i, dev in enumerate(jax.devices()[:4]):
        stats = dev.memory_stats() or {}
        log(f"device{i} peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    from repro.launch.platform import configure_compile_cache
    cache_dir = configure_compile_cache()
    import jax
    global CACHE
    CACHE = CacheLog()
    devices = require_tpu(args.chips)
    log(f"device: kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    log(f"compile_cache_dir: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips()
    else:
        stage_log = run_one_chip()
        kernel_stages = {k: v for k, v in stage_log.items()
                         if k.startswith(("fbuild_k", "prepare", "sample"))}
        for stage in ("fbuild_k", "prepare", "sample"):
            found = [v["tpu_custom_call"] for k, v in kernel_stages.items()
                     if k.startswith(stage)]
            assert found and all(found), (stage, kernel_stages)
        log("mosaic: tpu_custom_call in fbuild_k, prepare and sample")
    log(f"compile_cache: hits={CACHE.count('hit')} "
        f"misses={CACHE.count('miss')} not_kept={CACHE.count('not_kept')}")
    for kind in ("miss", "not_kept"):
        log(f"compile_cache_{kind}: {sorted(CACHE.names[kind])}")
    log(f"total_s: {time.perf_counter() - t0:.3f} "
        f"result_unix_s: {time.time():.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    faulthandler.dump_traceback_later(EXIT_WATCH_S)


if __name__ == "__main__":
    main()
