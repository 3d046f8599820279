"""Unified telemetry layer (ISSUE 9): the metrics registry as the single
store behind every diagnostics snapshot, bounded span tracing with Chrome
trace-event export, per-path byte reconciliation (modeled vs metered), and
telemetry crash safety (snapshot/restore round trip + the failover drill).

This file is owned by the CI "async serving" leg (8 host devices) and
excluded everywhere else — keep it runnable on 1 device: multi-device
cases must skip, not fail.
"""

import gc
import json
import os
import subprocess
import sys
import threading
from collections import Counter

import jax
import numpy as np
import pytest

from repro.core.budget import QueryBudget
from repro.core.plan import Plan, PlanNode
from repro.core.relation import relation
from repro.core.window import WindowSpec
from repro.launch.trace_dump import summarize
from repro.runtime.async_serve import AsyncJoinFrontDoor, AsyncJoinServer
from repro.runtime.fault import InjectedFault
from repro.runtime.join_serve import (JoinRequest, JoinServer,
                                      ServerDiagnostics)
from repro.runtime.stream_join import StreamJoinServer
from repro.runtime.telemetry import (NULL_SPAN, MetricsRegistry, Tracer,
                                     chrome_trace, dump_chrome_trace,
                                     latency_pcts, span_tree,
                                     validate_chrome_trace)

MS, BM = 512, 256   # max_strata / b_max used throughout
ERR = QueryBudget(error=0.5)


def _mb(seed, n=256):
    r = np.random.default_rng(seed)
    return [relation(r.integers(0, 200, n).astype(np.uint32),
                     r.normal(10, 2, n).astype(np.float32)),
            relation(r.integers(150, 350, n).astype(np.uint32),
                     r.normal(5, 1, n).astype(np.float32))]


def _req(seed, qid="t0/q", **kw):
    kw.setdefault("rels", _mb(seed))
    kw.setdefault("budget", ERR)
    return JoinRequest(query_id=qid, seed=seed, max_strata=MS, b_max=BM,
                       **kw)


def _mesh(k):
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:k]), ("data",))


def _identical(a, b):
    return (float(a.estimate) == float(b.estimate)
            and float(a.error_bound) == float(b.error_bound)
            and float(a.count) == float(b.count)
            and float(a.dof) == float(b.dof))


# -- metrics registry --------------------------------------------------------

def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    c.inc()
    c.inc(2)
    assert reg.counter("hits") is c and c.value == 3
    assert "hits" in reg and "nope" not in reg
    with pytest.raises(TypeError):
        reg.gauge("hits")          # same name, different kind
    h = reg.histogram("lat", cap=3)
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.samples == [2.0, 3.0, 4.0]      # ring bounded at cap
    assert h.count == 4 and h.total == 10.0  # cumulative survive the ring


def test_registry_to_dict_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("serve_queries").inc(5)
    reg.gauge("load").set(0.5)
    reg.gauge("per_device.bytes").set(np.array([1.0, 2.0]))
    h = reg.histogram("lat")
    h.observe(1.0)
    d = reg.to_dict()
    assert d["serve_queries"] == 5 and d["load"] == 0.5
    assert d["per_device.bytes"] == [1.0, 2.0]
    assert d["lat"]["count"] == 1
    json.dumps(d)                            # JSON-able view

    text = reg.prometheus(prefix="repro")
    assert "# TYPE repro_serve_queries counter" in text
    assert "repro_serve_queries 5.0" in text
    # vector gauge -> one sample per device; dots sanitized
    assert 'repro_per_device_bytes{device="0"} 1.0' in text
    assert 'repro_per_device_bytes{device="1"} 2.0' in text
    assert 'repro_lat{quantile="0.5"} 1.0' in text
    assert "repro_lat_count 1" in text and "repro_lat_sum 1.0" in text
    # never-set scalar gauges are omitted, not exported as garbage
    reg.gauge("unset")
    assert "unset" not in reg.prometheus()


def test_latency_pcts_schema():
    z = latency_pcts([], "queue_latency")
    assert z == {"queue_latency_p50_s": 0.0, "queue_latency_p95_s": 0.0,
                 "queue_latency_max_s": 0.0}
    p = latency_pcts([1.0, 2.0, 3.0], "x")
    assert p["x_p50_s"] == 2.0 and p["x_max_s"] == 3.0


# -- tracer ------------------------------------------------------------------

def test_tracer_disabled_noop_and_ring_bounded():
    off = Tracer(enabled=False)
    assert off.span("s") is NULL_SPAN
    with off.span("s") as s:
        s.set(k=1)                            # no-op, no error
    off.instant("i")
    off.event("e", 0.0, 1.0)
    off.note_recon({"path": "x", "pairs": []})
    assert not off.events and not off.recon and off._seq == 0

    on = Tracer(enabled=True, capacity=8)
    for i in range(20):
        on.instant(f"i{i}")
    assert len(on.events) == 8                # ring bounded
    assert on._seq == 20                      # ids keep advancing
    assert [e["name"] for e in on.events][0] == "i12"


def test_tracer_state_adopt_max_merge():
    a, b = Tracer(enabled=True), Tracer(enabled=True)
    for _ in range(5):
        a.next_id()
    b.next_id()
    st = a.state()
    json.dumps(st)                            # rides snapshot meta
    b.adopt(st)
    assert b._seq == 5
    a.adopt(b.state())                        # max-merge: never regresses
    assert a._seq == 5
    assert b.next_id() == 6                   # successor ids stay unique


def test_span_tree_containment_and_zero_dur_leaves():
    tr = Tracer(enabled=True)
    tr.event("outer", 0.0, 10.0, tid="L")
    tr.event("inner", 1.0, 4.0, tid="L")
    tr.event("leaf", 2.0, 0.0, tid="L")       # zero-dur marker inside inner
    tr.event("mark", 2.0, 0.0, tid="L")       # same ts: must NOT nest in leaf
    tr.event("sibling", 6.0, 2.0, tid="L")
    tr.event("other-lane", 0.0, 1.0, tid="M")
    tr.instant("note", tid="L")               # instants are not tree nodes
    forest = span_tree(tr.events)
    roots = {n["name"] for n in forest}
    assert roots == {"outer", "other-lane"}
    outer = next(n for n in forest if n["name"] == "outer")
    assert [c["name"] for c in outer["children"]] == ["inner", "sibling"]
    inner = outer["children"][0]
    assert [c["name"] for c in inner["children"]] == ["leaf", "mark"]
    assert all(not c["children"] for c in inner["children"])


def test_chrome_trace_export_and_validation():
    tr = Tracer(enabled=True, tags={"replica": "r0"})
    tr.event("work", 1.0, 0.5, cat="serve", tid="engine", k=1)
    tr.instant("done", tid="engine")
    obj = chrome_trace(tr, reconciliation={"paths": {}, "server": [],
                                           "queries": []})
    n = validate_chrome_trace(obj)
    assert n == len(obj["traceEvents"])
    phs = {e["ph"] for e in obj["traceEvents"]}
    assert {"X", "i", "M"} <= phs             # spans, instants, metadata
    x = next(e for e in obj["traceEvents"] if e["ph"] == "X")
    assert x["ts"] == pytest.approx(1.0e6)    # microseconds
    assert x["args"]["replica"] == "r0"
    assert obj["displayTimeUnit"] == "ms"
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace([])


# -- diagnostics on the registry (satellites a, b, c) ------------------------

def test_server_snapshot_readonly_idempotent():
    srv = JoinServer(batch_slots=2)
    for s in range(3):
        srv.submit(_req(s, qid=f"t{s % 2}/q"))
    srv.run()
    snap1 = srv.diagnostics.snapshot()
    snap2 = srv.diagnostics.snapshot()
    assert snap1 == snap2                     # idempotent, mutates nothing
    assert snap1["queries"] == 3
    assert len(srv.diagnostics.queue_latencies) == 3  # rings untouched
    json.dumps(snap1)                         # JSON-able
    # the legacy attribute surface still reads through
    assert srv.diagnostics.queries == 3
    assert len(srv.diagnostics.tenant_latencies) == 2
    # the registry is the single backing store: prometheus sees it all
    text = srv.diagnostics.prometheus()
    assert "repro_serve_queries 3.0" in text
    assert "repro_serve_queue_latencies_count 3" in text
    # reset clears rings, keeps cumulative counters
    srv.diagnostics.reset_latencies()
    assert srv.diagnostics.queue_latencies == []
    assert srv.diagnostics.snapshot()["queries"] == 3


def test_tenant_rings_lru_bounded():
    d = ServerDiagnostics(tenant_cap=4)
    for i in range(4):
        d.note_latency(f"t{i}", 0.1, 0.2, cap=16)
    d.note_latency("t0", 0.1, 0.2, cap=16)    # touch t0: now most recent
    d.note_latency("t4", 0.1, 0.2, cap=16)    # evicts t1 (LRU), not t0
    per = d.tenant_latencies
    assert set(per) == {"t0", "t2", "t3", "t4"}
    assert d.tenant_evictions == 1
    for i in range(5, 10):
        d.note_latency(f"t{i}", 0.1, 0.2, cap=16)
    assert len(d.tenant_latencies) == 4
    assert d.tenant_evictions == 6
    assert len(d.snapshot()["per_tenant"]) == 4


def test_stream_diagnostics_schema_alignment():
    srv = StreamJoinServer(batch_slots=2)
    sd = srv.stream_diagnostics
    # one registry behind both diagnostics objects
    assert sd.registry is srv.diagnostics.registry
    snap = sd.snapshot()
    for k in ("window_latency_p50_s", "window_latency_p95_s",
              "window_latency_max_s"):
        assert snap[k] == 0.0                 # same pct schema as batch
    sess = srv.open_stream("t", WindowSpec(size=2, slide=1, sub_rows=256),
                           budget=ERR, max_strata=MS, b_max=BM, seed=3)
    for t in range(3):
        sess.push(_mb(100 + t))
        srv.run()
    done = sess.drain()
    assert done
    snap = sd.snapshot()
    assert snap == sd.snapshot()              # idempotent
    assert snap["windows_served"] == len(done)
    assert snap["window_latency_p95_s"] >= snap["window_latency_p50_s"] > 0
    assert "repro_stream_windows_served" in sd.registry.prometheus()


# -- end-to-end span trees + reconciliation per serving path -----------------

def _roots(srv, qid):
    forest = srv.query_trace(qid)
    return [n for n in forest if n["name"] == "query"]


def _span_names(node, acc=None):
    acc = set() if acc is None else acc
    acc.add(node["name"])
    for c in node["children"]:
        _span_names(c, acc)
    return acc


def test_single_device_span_tree_and_recon():
    tr = Tracer(enabled=True)
    srv = JoinServer(batch_slots=2, tracer=tr)
    srv.submit(_req(0, qid="t0/q"))           # error budget -> sampled
    srv.submit(_req(1, qid="t1/q", budget=QueryBudget()))   # exact
    srv.run()
    for qid, stage in (("t0/q", "sample"), ("t1/q", "exact")):
        roots = _roots(srv, qid)
        assert len(roots) == 1
        names = _span_names(roots[0])
        assert {"query", "queued", "execute", "prepare", stage} <= names
        kids = {c["name"] for c in roots[0]["children"]}
        assert {"queued", "execute"} <= kids
    # ingest + complete instants bracket every query
    for name in ("ingest", "complete"):
        assert any(e["name"] == name for e in tr.events)
    validate_chrome_trace(chrome_trace(tr))

    rep = srv.reconciliation_report()
    agg = rep["paths"]["single"]
    assert agg["filter_exchange_bytes"]["modeled"] > 0
    assert agg["live_tuple_bytes"]["measured"] is None   # no wire meter
    assert {p["name"] for p in rep["server"]} == {
        "filter_exchange_bytes", "dist_wire_bytes_model",
        "kernel_gather_bytes"}
    # always-on model counter advanced even though amortized meter is n/a
    assert srv.diagnostics.filter_exchange_bytes_model > 0


def test_tracing_off_serves_bit_identical_and_silent():
    hooks = list(gc.callbacks)
    on = JoinServer(batch_slots=2, tracer=Tracer(enabled=True))
    off = JoinServer(batch_slots=2)
    a = on.submit(_req(5, qid="t/q"))
    b = off.submit(_req(5, qid="t/q"))
    on.run()
    off.run()
    assert _identical(a.result, b.result)
    assert not off.tracer.events and not off.tracer.recon
    assert off.query_trace("t/q") == []
    assert off.reconciliation_report()["paths"] == {}
    # the async tier untraced: no collection hook, no loop span, same answer
    with AsyncJoinServer(JoinServer(batch_slots=2), name="r0") as srv:
        assert gc.callbacks == hooks
        c = srv.submit(_req(5, qid="t/q")).result(timeout=120)
    assert _identical(c.result, b.result)
    assert gc.callbacks == hooks
    assert not srv.tracer.events


def test_traced_step_reads_the_device_no_more_than_untraced(monkeypatch):
    """The tracer adds no device read to a step: its reconciliation meters
    stay on the device until a report is built.  Untraced, the step waits
    only on prepare (``d_filter`` needs it); traced, also on each stage it
    times."""
    counts = Counter()
    get, block = jax.device_get, jax.block_until_ready

    def counted_get(x):
        counts["get"] += 1
        return get(x)

    def counted_block(x):
        counts["block"] += 1
        return block(x)
    monkeypatch.setattr(jax, "device_get", counted_get)
    monkeypatch.setattr(jax, "block_until_ready", counted_block)

    def one_step(srv, seed):
        # a dataset handle: its filter words are cached after the first step
        srv.submit(_req(seed, qid="t0/q", rels=None, dataset="ds"))
        srv.submit(_req(seed, qid="t1/q", rels=None, dataset="ds",
                        budget=QueryBudget()))
        counts.clear()
        assert srv.step() == 2
        return dict(counts)

    got = {}
    for tracer in (None, Tracer(enabled=True)):
        srv = JoinServer(batch_slots=2, tracer=tracer)
        srv.register_dataset("ds", _mb(3))
        one_step(srv, 2)                                         # warm-up
        got[tracer is not None] = one_step(srv, 2)
    off, on = got[False], got[True]
    assert off["block"] == 1
    assert on["block"] == off["block"] + 2                       # sample, exact
    assert on["get"] == off["get"]
    assert srv.reconciliation_report()["paths"]["single"]        # read later


# a traced step is tiled by its host phases and stages, in this order
_PHASES = ("inputs", "prepare", "decide", "sample|exact", "finish")


def _check_phases(events, lane):
    """The last traced step on ``lane``: ``inputs``, ``prepare``,
    ``decide``, ``sample``/``exact`` and ``finish`` follow one another
    without overlap inside the ``step`` span and cover >= 90% of it; the
    ``complete`` span follows the step."""
    mine = [e for e in events if e["tid"] == lane and e["dur"] is not None]
    step = max((e for e in mine if e["name"] == "step"),
               key=lambda e: e["ts"])
    lo, hi, eps = step["ts"], step["ts"] + step["dur"], 1e-6
    names = {n for p in _PHASES for n in p.split("|")}
    inner = sorted((e for e in mine if e["name"] in names
                    and lo - eps <= e["ts"] <= hi + eps),
                   key=lambda e: e["ts"])
    order = [e["name"] for e in inner]
    assert order[:3] == ["inputs", "prepare", "decide"], order
    assert order[-1] == "finish", order
    assert set(order[3:-1]) and set(order[3:-1]) <= {"sample", "exact"}, \
        order
    for a, b in zip(inner, inner[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + eps, (a["name"], b["name"])
    assert inner[-1]["ts"] + inner[-1]["dur"] <= hi + eps
    assert sum(e["dur"] for e in inner) >= 0.9 * step["dur"], \
        [(e["name"], e["dur"]) for e in inner] + [("step", step["dur"])]
    complete = [e for e in mine if e["name"] == "complete"
                and e["ts"] >= hi - eps]
    assert complete and min(e["ts"] for e in complete) <= hi + 1e-3


_MESH_PHASES = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.budget import QueryBudget
from repro.core.relation import relation
from repro.runtime.join_serve import JoinRequest, JoinServer
from repro.runtime.telemetry import Tracer

r = np.random.default_rng(7)
rels = [relation(r.integers(0, 200, 256).astype(np.uint32),
                 r.normal(10, 2, 256).astype(np.float32)),
        relation(r.integers(150, 350, 256).astype(np.uint32),
                 r.normal(5, 1, 256).astype(np.float32))]
tr = Tracer(enabled=True)
srv = JoinServer(batch_slots=2, mesh=Mesh(np.array(jax.devices()), ("data",)),
                 tracer=tr)
srv.register_dataset("ds", rels)
for step in range(2):
    for i, budget in enumerate((QueryBudget(error=0.5), QueryBudget())):
        srv.submit(JoinRequest(dataset="ds", budget=budget, seed=step,
                               query_id=f"t{i}/q", max_strata=512,
                               b_max=256))
    assert srv.step() == 2
print("EVENTS " + json.dumps([e for e in tr.events if e["tid"] == "engine"]))
"""


@pytest.mark.parametrize("path", ["single", "kernel", "mesh4"])
def test_step_phases_tile_the_step(path):
    """On every serving path a traced step is tiled by its spans (the
    4-device mesh runs in a subprocess so the suite keeps one device)."""
    if path == "mesh4":
        env = dict(os.environ, PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", _MESH_PHASES], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-3000:]
        line = next(ln for ln in out.stdout.splitlines()
                    if ln.startswith("EVENTS "))
        events = json.loads(line[len("EVENTS "):])
        assert {e["args"]["path"] for e in events
                if e["name"] == "step"} == {"mesh4/exact-parity"}
    else:
        tr = Tracer(enabled=True)
        srv = JoinServer(batch_slots=2, tracer=tr)
        for seed in (0, 2):        # the first step compiles; check the next
            srv.submit(_req(seed, qid="t0/q", use_kernels=path == "kernel"))
            srv.submit(_req(seed + 1, qid="t1/q", budget=QueryBudget(),
                            use_kernels=path == "kernel"))
            assert srv.step() == 2
        events = list(tr.events)
    _check_phases(events, "engine")


def test_gc_span_on_the_engine_lane_and_hook_removed_on_close():
    """A collection forced from another thread while a traced async server
    is up lands on the replica's lane with the thread that ran it; close()
    takes the hook out again."""
    hooks = list(gc.callbacks)
    tr = Tracer(enabled=True)
    srv = AsyncJoinServer(JoinServer(batch_slots=2, tracer=tr), name="r0")
    try:
        assert len(gc.callbacks) == len(hooks) + 1
        srv.submit(_req(0, qid="t/q")).result(timeout=120)
        t = threading.Thread(target=gc.collect, name="collector")
        t.start()
        t.join(60)
        assert not t.is_alive()
    finally:
        srv.close()
    assert gc.callbacks == hooks
    spans = [e for e in tr.events if e["name"] == "gc"]
    assert any(e["tid"] == "r0" and e["cat"] == "gc"
               and e["args"]["thread"] == "collector"
               and e["args"]["generation"] == 2 for e in spans), spans
    n = len(tr.events)
    gc.collect()
    assert len(tr.events) == n                # nothing after close()


def test_gc_span_is_a_leaf_in_tree_and_export():
    """A collection that overlaps a stage span only in part (run by
    another thread) stays a leaf: span_tree hangs it under the span that
    holds it whole, and the Chrome export cuts it where its row's spans
    start or end, so every piece nests."""
    tr = Tracer(enabled=True)
    tr.event("step", 0.0, 10.0, tid="L")
    tr.event("prepare", 1.0, 4.0, cat="stage", tid="L")
    tr.event("decide", 5.0, 1.0, cat="engine", tid="L")
    tr.event("exact", 6.0, 3.0, cat="stage", tid="L")
    tr.event("gc", 4.0, 1.5, cat="gc", tid="L", thread="client")
    (step,) = span_tree(tr.events)
    assert [c["name"] for c in step["children"]] == [
        "prepare", "gc", "decide", "exact"]
    assert all(not c["children"] for c in step["children"])

    obj = chrome_trace(tr)
    validate_chrome_trace(obj)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    pieces = [e for e in xs if e["name"] == "gc"]
    assert [(p["ts"], p["dur"]) for p in pieces] == [(4e6, 1e6), (5e6, 5e5)]
    assert len({p["args"]["span_id"] for p in pieces}) == 1
    for a in xs:
        for b in xs:
            a0, a1 = a["ts"], a["ts"] + a["dur"]
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            assert (a1 <= b0 or b1 <= a0 or a0 <= b0 <= b1 <= a1
                    or b0 <= a0 <= a1 <= b1), (a["name"], b["name"])
            if a["name"] == "gc" and b["name"] != "gc":
                assert not (a0 <= b0 and b1 <= a1), b["name"]  # a leaf


def test_kernel_path_span_tree():
    tr = Tracer(enabled=True)
    srv = JoinServer(batch_slots=2, tracer=tr)
    r = srv.submit(_req(2, qid="tk/q", use_kernels=True))
    srv.run()
    assert r.done and r.result is not None
    root = _roots(srv, "tk/q")[0]
    assert root["args"]["path"] == "kernel"
    assert {"queued", "execute"} <= _span_names(root)
    rep = srv.reconciliation_report()
    assert "kernel" in rep["paths"]
    validate_chrome_trace(chrome_trace(tr))


@pytest.mark.parametrize("mode", ["exact-parity", "psum"])
def test_mesh_span_tree_and_recon_meters(mode):
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    k = 2
    tr = Tracer(enabled=True)
    srv = JoinServer(batch_slots=2, mesh=_mesh(k), serve_mode=mode,
                     tracer=tr)
    srv.register_dataset("ds", _mb(7))
    for s in range(2):
        srv.submit(_req(s, qid="tm/q", rels=None, dataset="ds"))
    srv.run()
    assert tr.tags.get("mesh") == "2"         # mesh-tagged events
    root = _roots(srv, "tm/q")[0]
    assert root["args"]["path"] == f"mesh{k}/{mode}"
    assert "shuffle" in _span_names(root)     # metered marker present
    rep = srv.reconciliation_report()
    agg = rep["paths"][f"mesh{k}/{mode}"]
    # on a mesh the tuple-byte model has a real meter: error is reported
    assert agg["live_tuple_bytes"]["measured"] is not None
    assert agg["live_tuple_bytes"]["rel_error"] is not None
    assert agg["dist_wire_bytes_model"]["measured"] is not None
    # per-device breakdown rides each query record
    recs = [r for r in rep["queries"] if r["path"] == f"mesh{k}/{mode}"]
    assert recs and all(len(r["per_device"]["measured"]) == k for r in recs)
    # the amortized filter-exchange meter counted actual mesh word builds
    fe = next(p for p in rep["server"]
              if p["name"] == "filter_exchange_bytes")
    assert fe["measured"] is not None and fe["measured"] > 0
    validate_chrome_trace(chrome_trace(tr))


def test_stream_window_spans():
    tr = Tracer(enabled=True)
    srv = StreamJoinServer(batch_slots=2, tracer=tr)
    sess = srv.open_stream("t", WindowSpec(size=2, slide=1, sub_rows=256),
                           budget=ERR, max_strata=MS, b_max=BM, seed=3)
    for t in range(3):
        sess.push(_mb(200 + t))
        srv.run()
    done = sess.drain()
    assert done
    served = {r.window_id for r in done}
    winq = [e for e in tr.events if e["name"] == "query"
            and e["args"].get("window") is not None]
    assert {e["args"]["window"] for e in winq} == served
    assert all(e["args"]["stream"] == "t" for e in winq)
    validate_chrome_trace(chrome_trace(tr))


def test_plan_node_spans_and_node_model_recon():
    tr = Tracer(enabled=True)
    srv = JoinServer(batch_slots=4, tracer=tr)
    r = np.random.default_rng(9)
    for name in "abc":
        keys = r.integers(0, 150, 256).astype(np.uint32)
        vals = r.normal(8, 2, 256).astype(np.float32)
        srv.register_dataset(name, [relation(keys, vals)])
    plan = Plan((PlanNode("ab", ("a", "b"), budget=ERR),
                 PlanNode("abc", ("ab", "c"), budget=ERR)))
    handle = srv.submit_plan(plan, query_id="p0", seed=7)
    srv.run()
    assert handle.done
    pe = next(e for e in tr.events if e["name"] == "plan")
    assert pe["args"]["hierarchy"] == {"ab": [], "abc": ["ab"]}
    for node in ("ab", "abc"):
        root = _roots(srv, f"p0/{node}")[0]
        assert root["args"]["plan"] == "p0"
        assert root["args"]["plan_node"] == node
    rep = srv.reconciliation_report()
    nm = rep["paths"]["single"]["node_bytes_model"]
    assert nm["queries"] == 2
    # the compile-time model re-stated at serve time: metered, small error
    assert nm["rel_error"] is not None
    validate_chrome_trace(chrome_trace(tr))


# -- crash safety (satellite d) ---------------------------------------------

def test_telemetry_survives_snapshot_restore():
    tr = Tracer(enabled=True)
    srv = StreamJoinServer(batch_slots=2, tracer=tr)
    sess = srv.open_stream("t", WindowSpec(size=2, slide=1, sub_rows=256),
                           budget=ERR, max_strata=MS, b_max=BM, seed=3)
    for t in range(3):
        sess.push(_mb(300 + t))
        srv.run()
    flat, meta = srv.snapshot_state()
    assert meta["telemetry"] == {"seq": tr._seq}
    assert json.dumps(meta["stream_diag"])    # scalar form, JSON-able

    tr2 = Tracer(enabled=True)
    dst = StreamJoinServer(batch_slots=2, tracer=tr2)
    dst.restore_state(flat, meta)
    # successor span ids can never collide with the dead server's
    assert tr2._seq >= tr._seq
    assert tr2.next_id() > tr._seq
    # counters merged additively into the shared registry
    assert dst.stream_diagnostics.windows_served == \
        srv.stream_diagnostics.windows_served
    assert dst.diagnostics.queries == srv.diagnostics.queries


def test_failover_drill_keeps_ids_and_counters_consistent(tmp_path):
    """A replica killed mid-workload: the shared fleet tracer records the
    fault and the failover, every event id stays unique across the dead
    replica and its successor, and the successor's counters keep the
    tenant's history (adopted via the checkpoint's additive merge)."""
    tr = Tracer(enabled=True)
    with AsyncJoinFrontDoor(replicas=2, checkpoint_dir=str(tmp_path),
                            tracer=tr) as fd:
        for i in range(6):
            fd.submit(_req(i, qid=f"t{i % 2}/q{i}")).result(timeout=120)
        victim = fd._assign["t0"]
        victim.kill_after(0)
        victim._thread.join(60)
        assert isinstance(victim.error, InjectedFault)
        import time
        deadline = time.monotonic() + 60
        served = None
        while served is None and time.monotonic() < deadline:
            try:
                served = fd.submit(_req(99, qid="t0/q99")).result(timeout=60)
            except BaseException:             # the injected fault
                time.sleep(0.05)
        assert served is not None and served.result is not None
        snap = fd.snapshot()
    assert snap["failovers"] == 1
    names = [e["name"] for e in tr.events]
    assert "fault" in names and "failover" in names
    fo = next(e for e in tr.events if e["name"] == "failover")
    assert fo["args"]["dead"] == victim.name
    ids = [e["id"] for e in tr.events]
    assert len(ids) == len(set(ids))          # fleet-wide unique span ids
    # replica lanes stayed separate in the export
    lanes = {e["tid"] for e in tr.events if e["name"] == "step"}
    assert len(lanes) == 2
    validate_chrome_trace(chrome_trace(tr))


# -- trace_dump CLI surface --------------------------------------------------

def test_dump_and_summarize(tmp_path):
    tr = Tracer(enabled=True)
    srv = JoinServer(batch_slots=2, tracer=tr)
    srv.submit(_req(0, qid="t0/q"))
    srv.run()
    path = str(tmp_path / "trace.json")
    n = dump_chrome_trace(tr, path,
                          reconciliation=srv.reconciliation_report())
    with open(path) as fh:
        obj = json.load(fh)
    assert validate_chrome_trace(obj) == n
    text = summarize(obj)
    assert "events" in text and "by category:" in text
    assert "byte reconciliation" in text
    assert "filter_exchange_bytes" in text
