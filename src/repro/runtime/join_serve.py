"""Batched multi-tenant ApproxJoin serving engine — single-device or mesh.

The ``JoinServer`` batches ApproxJoin queries the way LLM serving engines
batch token decodes across slots.  A :class:`JoinRequest`
carries relations (or a named dataset handle), a :class:`QueryBudget`, the
aggregate/expression, and a tenant ``query_id``.  The engine:

* **buckets** every relation to a power-of-two capacity
  (:func:`repro.core.relation.place_rows`) so queries fall into a small
  number of *shape classes*;
* keeps a **compiled-executable cache** keyed by
  ``(stage, shape_class, batch)`` — repeat tenants never recompile.  Shape
  classes also key on the **mesh shape**, so a server can serve mixed
  single-device and distributed classes without collisions;
* **batches same-shape-class queries with vmap** across the
  filter-probe/sort/strata and sample/estimate stages, so one engine step is
  one fused device dispatch per stage regardless of how many tenants share
  it — and, when constructed with ``mesh=``, that one dispatch **spans all
  mesh devices** through ``core/distributed.py``'s shard_map pipeline;
* caches **per-dataset Bloom filter words** keyed by
  ``(relation fingerprint, num_blocks, seed)``: a registered dataset pays
  the filter build once, then every subsequent step reuses the cached words
  (``ServerDiagnostics.filter_builds`` / ``filter_cache_hits``);
* shares one :class:`SigmaRegistry` and :class:`CostModel` across tenants, so
  a repeated ``query_id`` gets the paper's §3.2-II adaptive sample sizing for
  free — and tenants never see each other's sigmas (the registry is keyed by
  ``query_id``).

Results are bit-identical to a direct :func:`repro.core.join.approx_join`
call on the same (bucketed) relations with the same seed — on a mesh too:
the distributed stages merge per-device strata/statistics back into the
canonical single-device slot layout before estimating, so a mesh of any size
reproduces the single-device arithmetic exactly (asserted across mesh sizes
1/2/4/8 in ``tests/test_join_serve_distributed.py``).

That bit-parity merge is the expensive one: per-stratum stats all_gather to
every device and the shuffle buckets default to the lossless worst case.  At
cluster scale the server can instead run ``serve_mode='psum'``: per-device
estimator parts merge with a single psum (the paper's own dataflow) and the
shuffle buckets are CAPACITY-PLANNED from the Bloom-intersection overlap
estimate taken at ``register_dataset`` time (the dry-run's overlap-hint
trick) — so the filter's data-movement saving reaches the wire of the
static-shape dataflow.  Rows beyond the plan are dropped *and counted*
(``ServerDiagnostics.dist_dropped_tuples``, per device in
``per_device_dropped_tuples``, per query in the result diagnostics).  psum
results agree with exact-parity up to float reassociation; the guarantee is
statistical, asserted by the accuracy gate (``tests/test_accuracy_gate.py``:
CLT-bounded relative error, nominal CI coverage, allocation-faithful
per-stratum draws, at mesh 1/2/4/8).  Shape classes key on
``(serve_mode, bucket_cap)`` too, so the two modes never collide in the
executable cache.

Per-query dynamic decisions (exact-affordable?  per-stratum ``b_i`` from the
budget + sigma feedback) stay on the host, exactly as in ``approx_join`` —
the driver role.  Sigma feedback lands *between engine steps*, which is why
the scheduler runs **cross-step sigma pipelining** (``sigma_pipeline``, on
by default): same-``query_id`` error-budget repeats co-batched into one step
would all see the registry state at dispatch time, so the scheduler defers
each repeat to the NEXT step — every execution sees the previous one's
measured sigma, bit-identical to a sequential driver — and fills the freed
slot with the next same-class query, so a queue with id diversity loses no
throughput (asserted in ``tests/test_join_serve.py``).

Scheduling is FIFO until the queue backs up past ``backlog_slots``, then
**deadline-aware**: latency-budget queries (deadline = submission +
``latency_s``) are served before error-budget/exact ones (deadline
infinity), FIFO on ties.  Queue latency is tracked as a bounded sample ring
and surfaced as p50/p95/max in ``ServerDiagnostics.snapshot()`` — the
distribution the admission policy consults (and the one ``serve_bench``
records).

``use_kernels`` queries are FIRST-CLASS batched citizens: kernel shape
classes flow through the same ``_batch_inputs``/``_run_batch`` machinery
and executable cache as the jnp classes, with kernel-backed stage
executables (``core.join.prepare_stage_kernels_batched`` /
``sample_stage_kernels_batched``) whose Pallas grids carry the slot
dimension themselves — a 2-D ``(batch_slot, key_block)`` sweep over the
stacked ``[B, num_blocks, 8]`` filter layout instead of a per-query loop.
Seeds (and the decoupled ``filter_seed``) are runtime array operands, so a
mixed-seed batch is one executable and N distinct seeds cost zero
recompiles; prebuilt/cached filter words (dataset cache, streaming window
OR-merges) feed the stacked probe directly.  The kernels are single-device:
a mesh server still serves them on the default device, gathering sharded
rows back to the host first — that round-trip is metered as
``ServerDiagnostics.kernel_gather_bytes`` (zero at mesh 1, where rows
already sit on the one device).

The streaming subsystem (``runtime/stream_join.py``) layers windowed
sessions on this engine: ``JoinRequest.filter_seed`` decouples the filter
hash from the sampling seed, ``_words`` carries a window's pre-merged
sub-window filter words past the per-dataset cache, and ``overlap_hint``
re-plans psum shuffle buckets from the session's rolling overlap estimate.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import cache, partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bloom
from repro.core.budget import QueryBudget
from repro.core.cost import CostModel, SigmaRegistry
from repro.core.distributed import (make_serve_exact, make_serve_exact_psum,
                                    make_serve_filter_build,
                                    make_serve_prepare, make_serve_sample,
                                    make_serve_sample_psum,
                                    planned_bucket_cap)
from repro.core.estimators import SumParts
from repro.core.join import (EXPRS, TUPLE_BYTES, JoinDiagnostics, JoinResult,
                             decide_sample_sizes, exact_stage,
                             filter_exchange_bytes, measured_sigma,
                             prepare_stage_kernels_batched, prepare_stage_pre,
                             sample_stage, sample_stage_kernels_batched)
from repro.core.plan import CompiledPlan, Plan, compile_plan
from repro.core.relation import (Relation, bucket_capacity, fingerprint,
                                 place_rows, shard_to_mesh)
from repro.runtime.telemetry import (NULL_TRACER, Histogram, MetricsRegistry,
                                     Tracer, latency_pcts, recon_pair,
                                     span_tree)
from repro.runtime.telemetry import reconciliation_report as _recon_report

DEFAULT_B_MAX = 2048
AGGS = ("sum", "count", "avg", "stdev")
SERVE_MODES = ("exact-parity", "psum")
# a traced step's host phases (name -> span category): engine-lane spans
# between and after the stage dispatches, none of them any one query's
_HOST_PHASES = {"inputs": "engine", "decide": "engine", "finish": "engine",
                "tracer": "tracer"}


def tenant_of(query_id: str) -> str:
    """Tenant key of a query id — the ``'/'``-prefix convention
    (``'tenantA/sum0'`` -> ``'tenantA'``; un-prefixed ids are their own
    tenant).  The front door shards and steals by this key, and per-tenant
    latency percentiles group by it."""
    return query_id.split("/", 1)[0]


def bloom_overlap_estimate(rels: Sequence[Relation], fp_rate: float = 0.01,
                           seed: int = 0) -> float:
    """Planning-time live-fraction estimate from the Bloom intersection.

    Builds one filter per input, ANDs them, probes every input against the
    join filter and returns surviving/total — the same estimate the dry-run
    feeds as ``overlap_hint`` to size capacity-planned shuffle buckets.
    Biased UP only (Bloom false positives), so a bucket plan with slack on
    top of it errs on the lossless side.  One-off host-side work at dataset
    registration; the serving hot path never pays it.
    """
    num_blocks = bloom.num_blocks_for(max(r.capacity for r in rels), fp_rate)
    filters = [bloom.build(r.keys, r.valid, num_blocks, seed) for r in rels]
    jf = bloom.intersect_all(filters)
    live = sum(int(jax.device_get(jnp.sum(r.valid & bloom.contains(jf,
                                                                   r.keys))))
               for r in rels)
    total = sum(int(jax.device_get(r.count())) for r in rels)
    return live / max(total, 1)


class ShapeClass(NamedTuple):
    """Static compilation signature of a query (the executable-cache key).

    ``mesh`` is ``()`` for a single-device server, else the ordered
    ``(axis name, axis size)`` pairs of the join axes — so the same query
    stream served on different meshes compiles (and caches) per mesh shape.
    ``serve_mode`` and ``bucket_cap`` are part of the key too: the psum and
    exact-parity pipelines are different programs with different shapes
    (the shuffle buffers are ``bucket_cap``-sized), so entries of one mode
    can never collide with — or evict compilations of — the other.
    """

    caps: tuple[int, ...]    # per-side bucketed capacities
    n_inputs: int
    max_strata: int
    b_max: int
    expr: str
    agg: str
    dedup: bool
    use_kernels: bool
    fp_rate: float
    confidence: float
    mesh: tuple = ()
    serve_mode: str = "exact-parity"
    bucket_cap: int = 0      # mesh classes only; 0 = single-device


@dataclass(eq=False)
class JoinRequest:
    """One tenant query: relations (or dataset handle) + budget + query id.

    ``eq=False``: requests are identities, not values — a generated
    ``__eq__`` would compare the relation arrays (ambiguous-truth-value
    errors from jnp) and queue bookkeeping must never conflate two requests
    that happen to carry equal payloads.
    """

    rels: Optional[Sequence[Relation]] = None
    dataset: Optional[str] = None
    # multi-dataset handle (plan-node requests): the fused stage joins the
    # concatenation of the named datasets' relation lists, each resolved
    # through the same fingerprint path as a single-dataset handle — so a
    # table shared by several plan nodes builds its filter words once
    datasets: Optional[Sequence[str]] = None
    budget: QueryBudget = QueryBudget()
    agg: str = "sum"
    expr: str = "sum"
    query_id: str = "q0"
    seed: int = 0
    fp_rate: float = 0.01
    max_strata: Optional[int] = None
    b_max: Optional[int] = DEFAULT_B_MAX
    dedup: bool = False
    use_kernels: bool = False
    serve_mode: Optional[str] = None   # None -> the server's default
    # filter-hash seed, decoupled from the sampling seed so a streaming
    # session can vary draws per window while reusing cached filter words
    # (None -> ``seed``, the classic coupled behaviour)
    filter_seed: Optional[int] = None
    # psum bucket planning: live-fraction estimate overriding the dataset's
    # registration-time one (streaming sessions re-plan from the rolling
    # measured overlap)
    overlap_hint: Optional[float] = None
    # streaming metadata (set by StreamJoinSession)
    stream: Optional[str] = None
    window_id: Optional[int] = None
    # plan metadata (set by submit_plan): the owning plan's id and this
    # request's node name within it — restore_state regroups requests
    # carrying these into live PlanHandles, so a failover never drops an
    # in-flight plan
    plan: Optional[str] = None
    plan_node: Optional[str] = None
    # filled by the server
    result: Optional[JoinResult] = None
    done: bool = False
    shed: bool = False                 # dropped by admission control, unserved
    queue_latency_s: float = 0.0       # ingest -> dispatch (batch former wait)
    e2e_latency_s: float = 0.0         # ingest -> complete
    _class: Optional[ShapeClass] = field(default=None, repr=False)
    _submit_t: float = field(default=0.0, repr=False)
    # ingest -> dispatch -> complete timestamps (perf_counter).  The async
    # tier stamps _ingest_t at front-door ingestion, BEFORE engine
    # admission, so queue latency covers the ingress ring too; the
    # synchronous path stamps it in submit() (== _submit_t).
    _ingest_t: float = field(default=0.0, repr=False)
    _dispatch_t: float = field(default=0.0, repr=False)
    _complete_t: float = field(default=0.0, repr=False)
    # per-query completion future (async tier); resolved by the engine's
    # on_done hook for served AND shed requests
    _future: Optional[object] = field(default=None, repr=False)
    _fps: Optional[list[str]] = field(default=None, repr=False)
    # prebuilt per-side filter words (e.g. the OR of cached sub-window
    # words); when set, the batch path uses them verbatim instead of
    # fetching through the per-dataset cache
    _words: Optional[list] = field(default=None, repr=False)
    # compile-time byte model of the owning plan node (submit_plan copies
    # the node's node_bytes_model dict here) — the reconciliation report
    # pairs its bytes_pushdown against the serve-time metered bytes
    _bytes_model: Optional[dict] = field(default=None, repr=False)
    # tracer span id grouping every span of this request's execution
    # (unique per request instance, survives failover via Tracer.adopt)
    _span_id: Optional[int] = field(default=None, repr=False)


@dataclass
class PlanHandle:
    """An in-flight plan: one engine request per plan node.

    Node requests ride the normal queue (their query ids are
    ``'<plan_id>/<node>'``, so the whole plan is one tenant to the front
    door) and the handle is just the grouping — the engine tracks live
    handles in ``JoinServer.plans`` and drops a handle once every node
    finished, and ``restore_state`` rebuilds handles from the requests'
    plan metadata after a failover.
    """

    plan_id: str
    requests: dict = field(default_factory=dict)   # node name -> JoinRequest

    @property
    def done(self) -> bool:
        return all(r.done or r.shed for r in self.requests.values())

    def results(self) -> dict:
        """node name -> JoinResult (finished nodes only)."""
        return {name: r.result for name, r in self.requests.items()
                if r.done and r.result is not None}


# ServerDiagnostics scalar counters in snapshot order, with their comments:
#   queries..kernel_queries — served-query counts by decision/backend
#   queue_latency_s/e2e_latency_s — summed ingest->dispatch / ->complete
#   plan_compiles/plan_cache_hits — compiled-plan cache misses/reuses
#   sigma_deferrals — same-id repeats pushed to the next step
#   deadline_promotions — backlog steps served out of FIFO order
#   filter_s/filter_build_s/filter_builds/filter_cache_hits — Bloom stage
#   shuffled_bytes_saved — repartition-vs-filtered delta over served queries
#   kernel_gather_bytes — host gather bytes for kernel queries on a mesh
#     server (zero at mesh 1 and meshless — asserted in tests)
#   dist_shuffled_tuple_bytes — measured live bytes moved (mesh only)
#   dist_dropped_tuples — shuffle rows dropped beyond the bucket plan
#     (always 0 under the lossless exact-parity default)
#   dist_wire_bytes_model — static per-device collective-buffer bytes (the
#     Eq. 24 serve-time wire model; what a dense dataflow puts on the wire)
#   filter_exchange_bytes_model — summed §3.1 (n+1)-exchange model over
#     served queries; its metered counterpart below counts ACTUAL word
#     bytes put on the wire by mesh filter builds (cache hits move none),
#     so the pair exposes the serving tier's filter-exchange amortization
#   tenant_evictions — per-tenant latency rings LRU-evicted past tenant_cap
_DIAG_SCALAR_FIELDS = (
    "queries", "steps", "cache_hits", "compiles", "exact_queries",
    "sampled_queries", "kernel_queries", "queue_latency_s", "e2e_latency_s",
    "plan_compiles", "plan_cache_hits", "sigma_deferrals",
    "deadline_promotions", "filter_s", "filter_build_s", "filter_builds",
    "filter_cache_hits", "shuffled_bytes_saved", "kernel_gather_bytes",
    "dist_shuffled_tuple_bytes", "dist_dropped_tuples",
    "dist_wire_bytes_model", "filter_exchange_bytes_model",
    "filter_exchange_bytes_measured", "tenant_evictions", "max_batch")
# per-device f64 [k] meters (mesh servers only; None elsewhere)
_DIAG_VECTOR_FIELDS = ("per_device_shuffled_bytes",
                       "per_device_dropped_tuples")


class ServerDiagnostics:
    """Server-level counters (cumulative since construction).

    Every field is backed by a :class:`repro.runtime.telemetry.MetricsRegistry`
    metric (scalars by counters, per-device meters by gauges, the latency
    rings by histograms) — the registry is the single store behind
    ``snapshot()``, the Prometheus export, and the stream diagnostics that
    share it.  Attribute access routes through the registry, so the classic
    ``diag.queries += 1`` call sites (and the additive restore merge) are
    unchanged.

    Per-tenant latency rings are LRU-bounded at ``tenant_cap`` distinct
    tenants (an adversarial tenant-id stream must not grow ``per_tenant``
    without limit); evictions are counted in ``tenant_evictions``.
    """

    _SCALARS = frozenset(_DIAG_SCALAR_FIELDS)
    _VECTORS = frozenset(_DIAG_VECTOR_FIELDS)

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tenant_cap: int = 256):
        self.registry = MetricsRegistry() if registry is None else registry
        self.tenant_cap = tenant_cap
        for f in _DIAG_SCALAR_FIELDS:
            self.registry.counter("serve_" + f)
        for f in _DIAG_VECTOR_FIELDS:
            self.registry.gauge("serve_" + f)
        # bounded rings of recent per-query latencies; snapshot() reduces
        # each to p50/p95/max (the distributions the deadline-aware
        # admission and the async tier's SLO reporting consult — a running
        # sum cannot see tail latency)
        self._q_hist = self.registry.histogram("serve_queue_latencies")
        self._e_hist = self.registry.histogram("serve_e2e_latencies")
        # tenant -> (queue Histogram, e2e Histogram), LRU order: a front
        # door reading one replica snapshot can attribute a latency
        # regression to a tenant
        self._tenants: OrderedDict = OrderedDict()

    def __getattr__(self, name):
        # only reached when normal lookup fails — i.e. the registry-backed
        # fields and the legacy ring views
        d = object.__getattribute__(self, "__dict__")
        reg = d.get("registry")
        if reg is not None:
            if name in self._SCALARS:
                return reg.counter("serve_" + name).value
            if name in self._VECTORS:
                return reg.gauge("serve_" + name).value
            if name == "queue_latencies":
                return d["_q_hist"].samples
            if name == "e2e_latencies":
                return d["_e_hist"].samples
            if name == "tenant_latencies":
                return {t: (qh.samples, eh.samples)
                        for t, (qh, eh) in d["_tenants"].items()}
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name in self._SCALARS:
            self.registry.counter("serve_" + name).value = value
        elif name in self._VECTORS:
            self.registry.gauge("serve_" + name).value = value
        else:
            object.__setattr__(self, name, value)

    def note_latency(self, tenant: str, queue_s: float, e2e_s: float,
                     cap: int) -> None:
        """Record one finished query's ingest->dispatch / ingest->complete
        latencies into the global and per-tenant bounded rings."""
        self.queue_latency_s += queue_s
        self.e2e_latency_s += e2e_s
        per = self._tenants.get(tenant)
        if per is None:
            per = (Histogram(f"tenant_queue_latencies/{tenant}", cap),
                   Histogram(f"tenant_e2e_latencies/{tenant}", cap))
            self._tenants[tenant] = per
            while len(self._tenants) > self.tenant_cap:
                self._tenants.popitem(last=False)
                self.tenant_evictions += 1
        else:
            self._tenants.move_to_end(tenant)
        for hist, x in ((self._q_hist, queue_s), (self._e_hist, e2e_s),
                        (per[0], queue_s), (per[1], e2e_s)):
            hist.cap = cap
            hist.observe(x)

    def reset_latencies(self) -> None:
        """Clear the latency sample rings (cumulative counters stay).  A
        bench reusing one warmed server calls this between timed segments
        so warmup-era samples cannot leak into a later segment's
        percentiles."""
        self._q_hist.reset_samples()
        self._e_hist.reset_samples()
        self._tenants.clear()

    @staticmethod
    def _pcts(lat, prefix: str) -> dict:
        return latency_pcts(lat, prefix)

    def scalars(self) -> dict:
        """The scalar counters as a plain dict (the crash-safe meta form)."""
        return {f: getattr(self, f) for f in _DIAG_SCALAR_FIELDS}

    def prometheus(self, prefix: str = "repro") -> str:
        """Prometheus text exposition of the backing registry."""
        return self.registry.prometheus(prefix)

    def snapshot(self) -> dict:
        """Point-in-time dict view — strictly read-only and idempotent:
        building a snapshot mutates nothing, and two consecutive snapshots
        of an idle server are equal (asserted in tests)."""
        d: dict = self.scalars()
        for f in _DIAG_VECTOR_FIELDS:
            v = getattr(self, f)
            d[f] = None if v is None else [float(x) for x in v]
        d.update(latency_pcts(self._q_hist.samples, "queue_latency"))
        d.update(latency_pcts(self._e_hist.samples, "e2e_latency"))
        d["per_tenant"] = {
            t: {"samples": len(qh.samples),
                **latency_pcts(qh.samples, "queue_latency"),
                **latency_pcts(eh.samples, "e2e_latency")}
            for t, (qh, eh) in self._tenants.items()}
        return d


def shape_class_of(req: JoinRequest, mesh_shape: tuple = (),
                   serve_mode: str = "exact-parity",
                   bucket_cap: int = 0) -> ShapeClass:
    caps = tuple(bucket_capacity(r.capacity) for r in req.rels)
    return ShapeClass(caps, len(caps), req.max_strata, req.b_max,
                      req.expr, req.agg, req.dedup, req.use_kernels,
                      req.fp_rate, req.budget.confidence, mesh_shape,
                      serve_mode, bucket_cap)


# Each stage function carries its stage's name: the device trace names an
# executable after it (``jit_serve_prepare(<hash>)`` on the XLA Modules line).

def _make_prepare(max_strata: int):
    def serve_prepare(rels, words, seed):
        return prepare_stage_pre(rels, words, max_strata, seed)
    return jax.jit(jax.vmap(serve_prepare))


def _make_sample(b_max: int, agg: str, dedup: bool, confidence: float,
                 expr: str):
    f_fn = EXPRS[expr][0]
    def serve_sample(sorted_rels, strata, b_i, seed):
        return sample_stage(sorted_rels, strata, b_i, b_max, seed,
                            agg=agg, dedup=dedup, confidence=confidence,
                            f_fn=f_fn)
    return jax.jit(jax.vmap(serve_sample))


def _make_exact(agg: str, expr: str):
    def serve_exact(sorted_rels, strata):
        return exact_stage(sorted_rels, strata, agg=agg, expr=expr)
    return jax.jit(jax.vmap(serve_exact))


def _make_filter_build(num_blocks: int):
    def serve_filter_build(keys, valid, seed):
        return bloom.build(keys, valid, num_blocks, seed).words
    return jax.jit(serve_filter_build)


# -- kernel-backed stage builders (Pallas grids own the slot dimension, so
# -- these take the engine's slot-stacked batch directly instead of vmap) ---

def _make_prepare_kernels(max_strata: int):
    def serve_prepare_kernels(rels, words, seeds):
        return prepare_stage_kernels_batched(rels, words, max_strata, seeds)
    return jax.jit(serve_prepare_kernels)


def _make_sample_kernels(b_max: int, agg: str, confidence: float, expr: str):
    def serve_sample_kernels(sorted_rels, strata, b_i, seeds):
        return sample_stage_kernels_batched(
            sorted_rels, strata, b_i, b_max, seeds, agg=agg,
            confidence=confidence, expr=expr)
    return jax.jit(serve_sample_kernels)


def _make_filter_build_kernels(num_blocks: int):
    from repro.kernels import ops as kops

    def serve_filter_build_kernels(keys, valid, seed):
        return kops.build_filter(keys, valid, num_blocks, seed).words
    return jax.jit(serve_filter_build_kernels)


def _metered_pairs(fetch, i: int, fe_model: float, wire: float, k: int,
                   bytes_model: Optional[dict]) -> dict:
    """Slot ``i``'s reconciliation pairs from the step's meters (``fetch()``
    -> host ``(live_counts, shuffled_tuple_bytes, device_shuffled_bytes)``,
    the last two ``None`` off the mesh)."""
    live, tup, dev = fetch()
    live_model = float(np.asarray(live[i]).sum()) * TUPLE_BYTES
    # live-tuple bytes: §3.1's filtered-shuffle volume vs the metered
    # per-query tuple bytes actually moved (mesh only — single-device and
    # kernel queries move no wire tuples)
    pairs = [recon_pair("live_tuple_bytes", live_model,
                        None if tup is None else float(tup[i]))]
    # per-query filter exchange is modeled-only here: the measured
    # counterpart is cumulative and amortized across the word cache (see
    # the server-level pair in reconciliation_report)
    pairs.append(recon_pair("filter_exchange_bytes", fe_model, None))
    if tup is not None:
        # static collective-buffer model vs live tuple bytes: the gap is
        # the dense dataflow's buffer slack
        pairs.append(recon_pair("dist_wire_bytes_model", wire,
                                float(tup[i])))
    if bytes_model is not None:
        # compile-time plan-node model vs this execution's serve-time
        # restatement of the same §3.1 cost
        pairs.append(recon_pair("node_bytes_model",
                                float(bytes_model["bytes_pushdown"]),
                                live_model + fe_model))
    out = {"pairs": pairs}
    if dev is not None:
        out["per_device"] = {"modeled": [wire / k] * k,
                             "measured": [float(x) for x in dev[i]]}
    return out


class JoinServer:
    """Slot-based batched ApproxJoin engine (caller-driven ``step()`` loop;
    ``runtime/async_serve.py`` wraps it into an always-on event loop).

    ``mesh=None`` serves every batch on the default device.  With a
    ``jax.sharding.Mesh``, registered datasets are sharded over
    ``join_axes`` at :meth:`register_dataset` time and every engine step's
    fused dispatch runs through the shard_map pipeline — one batched step
    spans all mesh devices, with bit-identical results.

    ``bucket_cap`` bounds the per-(source, dest) shuffle buckets of the
    distributed path; the default (local rows) can never drop a row, which
    the bit-parity guarantee needs — tighter caps trade memory for counted
    overflow (surfaced in the result diagnostics).

    ``serve_mode`` picks the cluster-scale merge strategy (overridable per
    request):

    * ``'exact-parity'`` (default): gather merge, lossless buckets —
      bit-identical to the single-device pipeline at any mesh size.
    * ``'psum'``: single-psum merge of estimator parts + buckets
      capacity-planned from the dataset's Bloom-intersection overlap
      estimate — the paper's cheap-collective dataflow; accuracy is
      statistical (the accuracy gate), dropped rows are counted.
    """

    def __init__(self, *, batch_slots: int = 4,
                 cost_model: Optional[CostModel] = None,
                 sigma_registry: Optional[SigmaRegistry] = None,
                 mesh=None, join_axes: Optional[Sequence[str]] = None,
                 bucket_cap: Optional[int] = None,
                 serve_mode: str = "exact-parity",
                 filter_cache_entries: int = 256,
                 sigma_pipeline: bool = True,
                 backlog_slots: Optional[int] = None,
                 latency_samples: int = 4096,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None):
        assert serve_mode in SERVE_MODES, serve_mode
        self.serve_mode = serve_mode
        self.batch_slots = batch_slots
        # cross-step sigma pipelining: same-query_id error-budget repeats
        # are deferred to the NEXT step so each sees the previous
        # execution's measured sigma (sequential-feedback adaptive sizing);
        # slots freed by a deferral fill with other same-class queries
        self.sigma_pipeline = sigma_pipeline
        # queue length beyond which the scheduler goes deadline-aware:
        # latency-budget queries (deadline = submit + latency_s) are served
        # before error-budget/exact ones (deadline = infinity), FIFO on ties
        self.backlog_slots = 2 * batch_slots if backlog_slots is None \
            else backlog_slots
        self.latency_samples = latency_samples
        self.cost_model = cost_model
        self.sigma = SigmaRegistry() if sigma_registry is None \
            else sigma_registry
        self.queue: list[JoinRequest] = []
        self.datasets: dict[str, list[Relation]] = {}
        self._dataset_fps: dict[str, list[str]] = {}
        self._dataset_overlap: dict[str, float] = {}
        self._exec_cache: dict = {}
        # compiled plans, cached by plan signature the way shape classes key
        # the executable cache: resubmitting a plan shape skips the
        # flatten/validate/cost pass entirely (per-node stage executables
        # land in _exec_cache through the normal shape-class route)
        self._plan_cache: dict = {}
        self.plans: dict[str, PlanHandle] = {}   # in-flight plan handles
        # LRU of (fingerprint, num_blocks, seed) -> words: bounded so a
        # long-running server with ever-fresh seeds cannot accumulate
        # device-resident filter words without limit
        self._filter_words: OrderedDict = OrderedDict()
        self.filter_cache_entries = filter_cache_entries
        # telemetry: a disabled NULL_TRACER by default — span()/event()/
        # instant() early-return, so the untraced hot path pays one
        # attribute read per site.  The metrics registry is the single
        # backing store of the diagnostics (and of a StreamDiagnostics
        # sharing it); `tracer.tags` carries replica/mesh identity into
        # every recorded event.
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.trace_name = "engine"   # lane/replica label for step spans
        self.diagnostics = ServerDiagnostics(registry=metrics)
        # per-step scratch the tracer consumes (None while tracing is off)
        self._stage_trace: Optional[dict] = None
        self._recon_batch: Optional[dict] = None
        # completion callback (request -> None), fired by _notify_done for
        # every finished or shed request; the async tier installs its
        # future-resolver here
        self.on_done = None
        self.mesh = mesh
        self.bucket_cap = bucket_cap
        if mesh is not None:
            axes = tuple(join_axes) if join_axes is not None \
                else tuple(mesh.axis_names)
            assert all(a in mesh.axis_names for a in axes), (axes, mesh)
            self.join_axes = axes
            self.mesh_k = 1
            for a in axes:
                self.mesh_k *= mesh.shape[a]
            self.mesh_shape = tuple((a, mesh.shape[a]) for a in axes)
            self.diagnostics.per_device_shuffled_bytes = np.zeros(
                self.mesh_k, np.float64)
            self.diagnostics.per_device_dropped_tuples = np.zeros(
                self.mesh_k, np.float64)
        else:
            self.join_axes = ()
            self.mesh_k = 1
            self.mesh_shape = ()
        if tracer is not None and self.mesh is not None:
            tracer.tags.setdefault(
                "mesh", "x".join(str(s) for _, s in self.mesh_shape))

    # -- admission ----------------------------------------------------------

    def _admit_rels(self, rels: Sequence[Relation]) -> list[Relation]:
        return [place_rows(r, bucket_capacity(r.capacity, self.mesh_k),
                           self.mesh, self.join_axes) for r in rels]

    def register_dataset(self, name: str, rels: Sequence[Relation]) -> None:
        """Store a named (bucketed, mesh-sharded) dataset for handle queries.

        Fingerprints are taken here, once — N steps over the dataset build
        its Bloom filter words exactly once per ``(num_blocks, seed)``, and
        re-registering identical relations under a new name reuses the same
        cached words.  On a mesh the Bloom-intersection overlap estimate is
        also taken here (on the host copy, before device placement) — it
        sizes the capacity-planned shuffle buckets of psum-mode queries.
        """
        if self.mesh is not None:
            self._dataset_overlap[name] = bloom_overlap_estimate(rels)
        self.datasets[name] = self._admit_rels(rels)
        self._dataset_fps[name] = [fingerprint(r) for r in self.datasets[name]]

    def submit(self, req: JoinRequest) -> JoinRequest:
        if req.rels is None:
            if req.datasets is not None:
                for name in req.datasets:
                    if name not in self.datasets:
                        raise ValueError(f"unknown dataset {name!r}")
                req.rels = [r for name in req.datasets
                            for r in self.datasets[name]]
                req._fps = [fp for name in req.datasets
                            for fp in self._dataset_fps[name]]
            elif req.dataset is not None:
                req.rels = self.datasets[req.dataset]
                req._fps = self._dataset_fps[req.dataset]
            else:
                raise ValueError("JoinRequest needs rels or a dataset handle")
        else:
            # inline relations are NOT fingerprinted: hashing every ad-hoc
            # submission would put a device_get + sha1 of the whole key set
            # on the admission hot path to feed a cache that only pays off
            # for repeated identical key sets — that contract belongs to
            # register_dataset.  Their filter words build per step, uncached.
            req.rels = self._admit_rels(req.rels)
            req._fps = [None] * len(req.rels)
        if len(req.rels) < 2:
            raise ValueError("join needs at least two relations")
        if req.expr not in EXPRS:
            raise ValueError(f"unknown expr {req.expr!r}")
        if req.agg not in AGGS:
            raise ValueError(f"unknown agg {req.agg!r}")
        if req.max_strata is None:
            # size from the LARGEST input (mirrors approx_join): the old
            # rels[0] default under-sized the strata grid whenever a later
            # relation was bigger, silently inflating strata_overflow
            req.max_strata = max(r.capacity for r in req.rels)
        if req.b_max is None:
            # approx_join's b_max=None adaptive grid sizes the draw capacity
            # from data-dependent peak b_i — incompatible with a pre-keyed
            # executable cache, so refuse rather than silently diverge.
            raise ValueError("JoinServer needs a concrete b_max "
                             f"(e.g. the default {DEFAULT_B_MAX}); the "
                             "adaptive b_max=None grid is driver-side only")
        mode = req.serve_mode or self.serve_mode
        if mode not in SERVE_MODES:
            raise ValueError(f"unknown serve_mode {mode!r}")
        if self.mesh is None or req.use_kernels:
            # psum vs exact-parity only distinguishes mesh merge strategies;
            # off-mesh (and on the single-device kernel route) there is one
            # pipeline and it IS the exact one
            mode = "exact-parity"
        req._class = shape_class_of(
            req, () if req.use_kernels else self.mesh_shape, mode,
            self._planned_cap(req, mode))
        req._submit_t = time.perf_counter()
        if not req._ingest_t:
            # async ingestion pre-stamps _ingest_t at the front door so the
            # ingress-ring wait counts; the synchronous path starts here
            req._ingest_t = req._submit_t
        if self.tracer.enabled:
            if req._span_id is None:
                req._span_id = self.tracer.next_id()
            self.tracer.instant(
                "ingest", cat="admission", tid=self.trace_name,
                ts=req._ingest_t, query_id=req.query_id,
                tenant=tenant_of(req.query_id), qspan=req._span_id)
        self.queue.append(req)
        return req

    # -- query plans --------------------------------------------------------

    def compile_plan(self, plan: Plan) -> CompiledPlan:
        """Compile (or fetch) a plan against this server's datasets.

        Flattening, validation, and the pushdown-vs-binary byte model run
        once per plan signature; repeats are cache hits.  Registering new
        data under a name already baked into a cached plan is fine — the
        compiled form only holds dataset *names*; relations resolve at
        submit time through the normal handle path.
        """
        key = plan.signature()
        compiled = self._plan_cache.get(key)
        if compiled is None:
            with self.tracer.span("plan-compile", cat="plan",
                                  tid=self.trace_name,
                                  nodes=len(plan.nodes)):
                compiled = compile_plan(plan, self.datasets)
            self._plan_cache[key] = compiled
            self.diagnostics.plan_compiles += 1
        else:
            self.diagnostics.plan_cache_hits += 1
        return compiled

    def submit_plan(self, plan: Plan, *, query_id: str = "plan0",
                    seed: int = 0, serve_mode: Optional[str] = None,
                    use_kernels: Optional[bool] = None) -> PlanHandle:
        """Submit every node of a plan as one engine request each.

        Node requests are ordinary queue entries (query id
        ``'<query_id>/<node>'``), so each node's result is bit-identical to
        a direct ``approx_join`` over its flattened leaf relations with the
        node's own budget — the compiler changes *what* is submitted, never
        how it executes.  The compiled byte model's live fraction seeds each
        request's ``overlap_hint`` (psum bucket planning).
        """
        compiled = self.compile_plan(plan)
        handle = PlanHandle(query_id)
        # plan -> node span hierarchy: node spans carry plan/plan_node args
        # and this instant carries the node-reference edges, so trace
        # consumers (trace_dump) can nest each node's query span under the
        # nodes that reference it
        self.tracer.instant("plan", cat="plan", tid=self.trace_name,
                            plan=query_id, hierarchy=plan.hierarchy())
        for cn in compiled.nodes:
            node = cn.node
            model = compiled.bytes_model.get(node.name)
            req = JoinRequest(
                datasets=cn.datasets, budget=node.budget, agg=node.agg,
                expr=node.expr, query_id=f"{query_id}/{node.name}",
                seed=seed, fp_rate=node.fp_rate, max_strata=node.max_strata,
                b_max=node.b_max, dedup=node.dedup,
                use_kernels=node.use_kernels if use_kernels is None
                else use_kernels,
                serve_mode=serve_mode,
                overlap_hint=None if model is None else model["overlap"],
                plan=query_id, plan_node=node.name)
            req._bytes_model = None if model is None else dict(model)
            self.submit(req)
            handle.requests[node.name] = req
        self.plans[query_id] = handle
        return handle

    def _planned_cap(self, req: JoinRequest, mode: str) -> int:
        """Static per-(source, dest) shuffle bucket capacity for this query.

        exact-parity: the lossless worst case (local rows) unless the server
        was constructed with an explicit ``bucket_cap``.  psum: planned from
        the dataset's registration-time Bloom overlap estimate with 2x slack
        (the dry-run's overlap-hint trick), pow2-bucketed so near-identical
        estimates share one compiled executable; inline relations (no
        registration, no estimate) fall back to overlap 1.0 — still the
        2x/k uniform-hashing plan, just not filter-informed.
        """
        if self.mesh is None or req.use_kernels:
            return 0
        local_n = max(bucket_capacity(r.capacity) for r in req.rels) \
            // self.mesh_k
        if self.bucket_cap:
            return min(self.bucket_cap, local_n)
        if mode != "psum":
            return local_n
        overlap = req.overlap_hint
        if overlap is None:
            overlap = self._dataset_overlap.get(req.dataset, 1.0)
        cap = planned_bucket_cap(local_n, self.mesh_k, overlap)
        return min(bucket_capacity(cap), local_n)

    # -- executable + filter-word caches ------------------------------------

    def _executable(self, stage: str, cls, variant, builder):
        """Fetch-or-build a compiled executable; ``variant`` is the rest of
        the cache key (batch bucket for vmapped stages, seed for the
        static-seed kernel route).  Returns (fn, freshly_built)."""
        key = (stage, cls, variant)
        fn = self._exec_cache.get(key)
        fresh = fn is None
        if fresh:
            fn = builder()
            self._exec_cache[key] = fn
            self.diagnostics.compiles += 1
        else:
            self.diagnostics.cache_hits += 1
        return fn, fresh

    def _words_for(self, rel: Relation, fp: Optional[str], num_blocks: int,
                   seed: int, use_kernels: bool = False) -> jnp.ndarray:
        """Per-relation dataset-filter words, built once per (fp, nb, seed).

        ``fp=None`` (inline relations) always builds — no cache entry.  On a
        mesh the build runs sharded (local build + OR-reduce) and the cached
        words are replicated — bit-identical to a single-device build.
        ``use_kernels`` routes a meshless build through the Pallas hash
        kernel; the words are bit-identical either way (asserted in
        ``tests/test_kernels.py``), so kernel and jnp queries share one
        word cache without divergence.
        """
        key = (fp, num_blocks, seed)
        if fp is not None:
            words = self._filter_words.get(key)
            if words is not None:
                self._filter_words.move_to_end(key)
                self.diagnostics.filter_cache_hits += 1
                return words
        t0 = time.perf_counter()
        if self.mesh is not None:
            build, _ = self._executable(
                "fbuild", (rel.capacity, num_blocks, self.mesh_shape), None,
                partial(make_serve_filter_build, self.mesh, self.join_axes,
                        num_blocks=num_blocks))
        elif use_kernels:
            build, _ = self._executable(
                "fbuild_k", (rel.capacity, num_blocks), None,
                partial(_make_filter_build_kernels, num_blocks))
        else:
            build, _ = self._executable(
                "fbuild", (rel.capacity, num_blocks), None,
                partial(_make_filter_build, num_blocks))
        words = build(rel.keys, rel.valid, jnp.uint32(seed))
        jax.block_until_ready(words)
        if self.mesh is not None and self.mesh_k > 1:
            # metered filter-exchange bytes: a mesh build OR-reduces local
            # words across k devices, putting ~(k-1) copies of the word
            # array on the wire; cache hits move nothing — so this meter
            # vs the per-query §3.1 model exposes the cache amortization
            self.diagnostics.filter_exchange_bytes_measured += \
                float(words.size * words.dtype.itemsize) * (self.mesh_k - 1)
        if fp is not None:
            self._filter_words[key] = words
            while len(self._filter_words) > self.filter_cache_entries:
                self._filter_words.popitem(last=False)
        self.diagnostics.filter_builds += 1
        self.diagnostics.filter_build_s += time.perf_counter() - t0
        return words

    # -- engine -------------------------------------------------------------

    def _deadline(self, req: JoinRequest) -> float:
        """Absolute serve-by time: latency budgets are deadlines, error and
        exact budgets are best-effort (infinite deadline)."""
        if req.budget.latency_s is None:
            return float("inf")
        # relative to INGESTION: through the async tier the caller's clock
        # starts when submit() returns the future, not when the event loop
        # admits the request (synchronously the two coincide)
        return req._ingest_t + req.budget.latency_s

    def _take_batch(self) -> tuple:
        """Pick the next step's shape class and batch.

        FIFO until the queue backs up past ``backlog_slots``; then
        deadline-aware — the class of the tightest-deadline request is
        served, and within the class candidates are ordered by deadline
        (stable, so all-error queues stay FIFO).  With ``sigma_pipeline``,
        at most one error-budget request per ``query_id`` joins a batch:
        the repeat is deferred one step so it sees this step's measured
        sigma (sequential-feedback adaptive sizing), and its slot fills
        with the next same-class query instead.
        """
        backlog = len(self.queue) > self.backlog_slots
        if backlog:
            head = min(self.queue, key=self._deadline)
            if head._class != self.queue[0]._class:
                self.diagnostics.deadline_promotions += 1
            cls = head._class
        else:
            cls = self.queue[0]._class
        candidates = [r for r in self.queue if r._class == cls]
        if backlog:
            candidates.sort(key=self._deadline)   # stable: FIFO on ties
        batch, seen_ids = [], set()
        for r in candidates:
            if len(batch) == self.batch_slots:
                break
            if (self.sigma_pipeline and r.budget.error is not None
                    and r.query_id in seen_ids):
                self.diagnostics.sigma_deferrals += 1
                continue
            batch.append(r)
            seen_ids.add(r.query_id)
        taken = set(map(id, batch))
        self.queue = [r for r in self.queue if id(r) not in taken]
        return cls, batch

    def step(self) -> int:
        """Serve one batch of same-shape-class queries; returns batch size."""
        if not self.queue:
            return 0
        t_form = time.perf_counter()
        cls, batch = self._take_batch()
        t_dispatch = time.perf_counter()
        self.diagnostics.steps += 1
        self.diagnostics.max_batch = max(self.diagnostics.max_batch,
                                         len(batch))
        self._run_batch(cls, batch)
        t_done = time.perf_counter()
        for req in batch:
            req._dispatch_t = t_dispatch
            req._complete_t = t_done
            req.queue_latency_s = t_dispatch - req._ingest_t
            req.e2e_latency_s = t_done - req._ingest_t
            req.done = True
            self.diagnostics.note_latency(
                tenant_of(req.query_id), req.queue_latency_s,
                req.e2e_latency_s, self.latency_samples)
            self.diagnostics.queries += 1
            d = req.result.diagnostics
            self.diagnostics.shuffled_bytes_saved += float(
                d.shuffled_bytes_repartition - d.shuffled_bytes_filtered)
            self._notify_done(req)
        if self.tracer.enabled:
            t_end = time.perf_counter()
            with self.tracer.span("tracer", cat="tracer",
                                  tid=self.trace_name):
                self._trace_step(cls, batch, t_form, t_dispatch, t_done,
                                 t_end)
        self._stage_trace = self._recon_batch = None
        return len(batch)

    def _path_of(self, cls: ShapeClass) -> str:
        """Serving-path tag for trace/reconciliation grouping."""
        if cls.use_kernels:
            return "kernel"
        if cls.mesh:
            return f"mesh{self.mesh_k}/{cls.serve_mode}"
        return "single"

    def _trace_step(self, cls: ShapeClass, batch: list[JoinRequest],
                    t_form: float, t_dispatch: float, t_done: float,
                    t_end: float) -> None:
        """Emit the step's spans: one engine-lane group (batch-formation,
        step, its host phases and stage timings, complete) plus a complete
        per-query span tree (query -> queued/execute ->
        prepare/filter-exchange/shuffle/sample|exact -> complete) on a lane
        per request instance, and the per-query byte reconciliation records
        collected by ``_run_batch``."""
        tr, lane, path = self.tracer, self.trace_name, self._path_of(cls)
        tr.event("batch-formation", t_form, t_dispatch - t_form, cat="batch",
                 tid=lane, batch=len(batch), path=path)
        tr.event("step", t_dispatch, t_done - t_dispatch, cat="serve",
                 tid=lane, batch=len(batch), path=path)
        stages = self._stage_trace or {}
        if "finish" in stages:
            ts, _, extra = stages["finish"]
            stages["finish"] = (ts, t_done - ts, extra)
        for name, (ts, dur, extra) in stages.items():
            tr.event(name, ts, dur, cat=_HOST_PHASES.get(name, "stage"),
                     tid=lane, path=path, **extra)
        # per-request bookkeeping after the step: latencies, the result
        # diagnostics' reads, completion futures and their callbacks
        tr.event("complete", t_done, t_end - t_done, cat="engine", tid=lane,
                 batch=len(batch), path=path)
        fe_model, recs = self._recon_batch or (0.0, {})
        for req in batch:
            tid = f"q:{req.query_id}#{req._span_id}"
            base = dict(query_id=req.query_id, qspan=req._span_id, path=path)
            if req.stream is not None:
                base.update(stream=req.stream, window=req.window_id)
            if req.plan is not None:
                base.update(plan=req.plan, plan_node=req.plan_node)
            tr.event("query", req._ingest_t,
                     req._complete_t - req._ingest_t, cat="query", tid=tid,
                     seed=req.seed, tenant=tenant_of(req.query_id), **base)
            tr.event("queued", req._ingest_t,
                     req._dispatch_t - req._ingest_t, cat="query", tid=tid,
                     **base)
            tr.event("execute", req._dispatch_t,
                     req._complete_t - req._dispatch_t, cat="query", tid=tid,
                     **base)
            for name, (ts, dur, extra) in stages.items():
                if name not in _HOST_PHASES:
                    tr.event(name, ts, dur, cat="stage", tid=tid, **base,
                             **extra)
            rec = recs.get(id(req))
            if rec is not None:
                tr.note_recon(rec)
                # zero-duration sub-phase markers (filter exchange and
                # shuffle are fused into the prepare dispatch — one XLA
                # program — so they mark, not span); the shuffle's metered
                # bytes stay on the device until reconciliation_report
                p_ts, p_dur, _ = stages.get("prepare",
                                            (req._dispatch_t, 0.0, None))
                tr.event("filter-exchange", p_ts + p_dur, 0.0, cat="stage",
                         tid=tid, modeled=fe_model, **base)
                tr.event("shuffle", p_ts + p_dur, 0.0, cat="stage",
                         tid=tid, **base)
            tr.instant("complete", cat="query", tid=tid,
                       ts=req._complete_t, **base)

    def _notify_done(self, req: JoinRequest) -> None:
        """Completion hook — fires once per finished OR shed request.  The
        async tier resolves the request's per-query future here; the hook
        runs after the result (or the shed flag) is fully populated."""
        if self.on_done is not None:
            self.on_done(req)
        if req.plan is not None:
            handle = self.plans.get(req.plan)
            if handle is not None and handle.done:
                del self.plans[req.plan]

    def run(self, max_steps: int = 10_000) -> None:
        for _ in range(max_steps):
            if self.step() == 0:
                break

    # -- crash safety: snapshot / restore -----------------------------------
    #
    # A snapshot is ``(flat arrays, meta)``: every device-resident piece of
    # engine state as a flat {key: array} dict (what runtime/checkpoint.py
    # serializes, one .npy + checksum per key) plus a JSON-able meta dict
    # carrying the host-side structure (dataset names/fingerprints, the
    # sigma table, queue descriptors, scalar counters).  Keys are
    # index-based (``ds/0/1/keys``) so user-chosen names never have to
    # round-trip through a file name.  NOT captured: the executable cache
    # (recompiles on the restoring server — a warmup cost, not state) and
    # in-flight latency timestamps (latency across a crash is ill-defined;
    # restored requests re-stamp at restore admission).

    # scalar diagnostics that survive a crash (cumulative counters; the
    # latency rings and per-device arrays restart empty)
    _DIAG_SCALARS = _DIAG_SCALAR_FIELDS

    @staticmethod
    def _req_meta(req: JoinRequest) -> dict:
        return {"dataset": req.dataset,
                "datasets": None if req.datasets is None
                else list(req.datasets),
                "plan": req.plan, "plan_node": req.plan_node,
                "budget": list(req.budget),
                "agg": req.agg, "expr": req.expr, "query_id": req.query_id,
                "seed": req.seed, "fp_rate": req.fp_rate,
                "max_strata": req.max_strata, "b_max": req.b_max,
                "dedup": req.dedup, "use_kernels": req.use_kernels,
                "serve_mode": req.serve_mode, "filter_seed": req.filter_seed,
                "overlap_hint": req.overlap_hint, "stream": req.stream,
                "window_id": req.window_id,
                "n_rels": len(req.rels) if req.rels is not None else 0,
                "n_words": 0 if req._words is None else len(req._words)}

    @staticmethod
    def _rel_arrays(flat: dict, prefix: str, r: Relation) -> None:
        flat[f"{prefix}/keys"] = r.keys
        flat[f"{prefix}/values"] = r.values
        flat[f"{prefix}/valid"] = r.valid

    def _rel_restore(self, flat: dict, prefix: str) -> Relation:
        r = Relation(jnp.asarray(flat[f"{prefix}/keys"]),
                     jnp.asarray(flat[f"{prefix}/values"]),
                     jnp.asarray(flat[f"{prefix}/valid"]))
        if self.mesh is not None:
            r = shard_to_mesh(r, self.mesh, self.join_axes)
        return r

    def snapshot_state(self) -> tuple[dict, dict]:
        """Capture the full serving state as ``(flat arrays, meta)``.

        Feed the pair to :func:`repro.runtime.checkpoint.save_checkpoint`
        (``tree=flat``, ``extra=meta``); the inverse is ``load_checkpoint``
        + :meth:`restore_state`.  The capture is synchronous with respect to
        engine mutation — call between steps (the async tier snapshots on
        its loop thread under the engine lock)."""
        flat: dict = {}
        meta: dict = {}
        ds_meta = []
        for di, (name, rels) in enumerate(self.datasets.items()):
            for i, r in enumerate(rels):
                self._rel_arrays(flat, f"ds/{di}/{i}", r)
            ds_meta.append({"name": name, "n": len(rels),
                            "fps": self._dataset_fps[name],
                            "overlap": self._dataset_overlap.get(name)})
        meta["datasets"] = ds_meta
        fw_keys = []
        for j, (key, words) in enumerate(self._filter_words.items()):
            fw_keys.append(list(key))            # [fp, num_blocks, seed]
            flat[f"fw/{j}"] = words
        meta["filter_cache"] = fw_keys           # in LRU order
        meta["sigma"] = {q: {str(k): float(v) for k, v in t.items()}
                         for q, t in self.sigma.table.items()}
        q_meta = []
        for j, req in enumerate(self.queue):
            m = self._req_meta(req)
            # handle requests (single- or multi-dataset) need no arrays: the
            # datasets themselves are in the snapshot and resolve by name
            if req.dataset is None and req.datasets is None:
                for i, r in enumerate(req.rels):
                    self._rel_arrays(flat, f"q/{j}/rels/{i}", r)
            if req._words is not None:           # pre-merged window words
                for i, w in enumerate(req._words):
                    flat[f"q/{j}/words/{i}"] = w
            q_meta.append(m)
        meta["queue"] = q_meta
        meta["diag"] = {f: getattr(self.diagnostics, f)
                        for f in self._DIAG_SCALARS}
        # span-id sequence: the successor adopting this snapshot must never
        # reuse this engine's span ids (Tracer.adopt max-merges)
        meta["telemetry"] = self.tracer.state()
        return flat, meta

    def restore_state(self, flat: dict, meta: dict) -> list[JoinRequest]:
        """Merge a snapshot into this engine; returns the re-queued requests.

        Merge semantics (not replace): restoring into a fresh engine is a
        plain restore, restoring into a live one ADOPTS the snapshot's
        tenants — the failover path, where a successor absorbs a dead
        replica's datasets, filter words, sigma entries (overwritten per
        query_id, continuing each sigma sequence exactly) and queued
        requests (appended in saved order, so same-``query_id`` FIFO — the
        only order sigma feedback observes — is preserved).  Served-but-
        undrained results are NOT part of a snapshot: their futures resolved
        at completion time, before any crash this snapshot survives."""
        for di, d in enumerate(meta.get("datasets", [])):
            rels = [self._rel_restore(flat, f"ds/{di}/{i}")
                    for i in range(d["n"])]
            self.datasets[d["name"]] = rels
            self._dataset_fps[d["name"]] = list(d["fps"])
            if d["overlap"] is not None:
                self._dataset_overlap[d["name"]] = d["overlap"]
        for j, key in enumerate(meta.get("filter_cache", [])):
            fp, num_blocks, seed = key
            self._filter_words[(fp, int(num_blocks), int(seed))] = \
                jnp.asarray(flat[f"fw/{j}"])
        while len(self._filter_words) > self.filter_cache_entries:
            self._filter_words.popitem(last=False)
        for q, t in meta.get("sigma", {}).items():
            self.sigma.table[q] = {int(k): float(v) for k, v in t.items()}
        restored = []
        for j, m in enumerate(meta.get("queue", [])):
            if m["dataset"] is None and not m.get("datasets"):
                rels = [self._rel_restore(flat, f"q/{j}/rels/{i}")
                        for i in range(m["n_rels"])]
            else:
                rels = None
            req = JoinRequest(
                rels=rels, dataset=m["dataset"], datasets=m.get("datasets"),
                budget=QueryBudget(*m["budget"]), agg=m["agg"],
                expr=m["expr"], query_id=m["query_id"], seed=m["seed"],
                fp_rate=m["fp_rate"], max_strata=m["max_strata"],
                b_max=m["b_max"], dedup=m["dedup"],
                use_kernels=m["use_kernels"], serve_mode=m["serve_mode"],
                filter_seed=m["filter_seed"], overlap_hint=m["overlap_hint"],
                stream=m["stream"], window_id=m["window_id"],
                plan=m.get("plan"), plan_node=m.get("plan_node"))
            if m["n_words"]:
                req._words = [jnp.asarray(flat[f"q/{j}/words/{i}"])
                              for i in range(m["n_words"])]
            self.submit(req)
            restored.append(req)
            if req.plan is not None:
                # regroup plan-node requests into a live handle so the
                # successor tracks (and completes) the adopted plan whole
                handle = self.plans.setdefault(req.plan,
                                               PlanHandle(req.plan))
                handle.requests[req.plan_node] = req
        for f, v in meta.get("diag", {}).items():
            if f == "max_batch":
                self.diagnostics.max_batch = max(self.diagnostics.max_batch,
                                                 v)
            else:
                setattr(self.diagnostics, f,
                        getattr(self.diagnostics, f) + v)
        tel = meta.get("telemetry")
        if tel and self.tracer is not NULL_TRACER:
            self.tracer.adopt(tel)
        return restored

    # -- execution paths ----------------------------------------------------

    def _kernel_gather(self, arrays) -> list:
        """Round-trip device arrays to the host for the kernel path (the
        Pallas kernels are single-device; a mesh server's rows/words are
        sharded or replicated across the mesh).  Metered: the batched
        kernel path must keep this at ZERO on meshless servers and mesh 1."""
        host = [np.asarray(jax.device_get(x)) for x in arrays]
        self.diagnostics.kernel_gather_bytes += float(
            sum(h.nbytes for h in host))
        return [jnp.asarray(h) for h in host]

    def _batch_inputs(self, cls: ShapeClass, batch: list[JoinRequest]):
        """Pad to the pow2 batch bucket; stack relations, words and seeds."""
        B = bucket_capacity(len(batch))
        reqs = batch + [batch[-1]] * (B - len(batch))  # pad slots (discarded)
        # kernel classes on a multi-device mesh serve on the default device:
        # sharded rows gather back to the host, once per DISTINCT array this
        # step (dataset-handle requests share Relation objects — B slots of
        # one dataset move its rows once, and kernel_gather_bytes counts
        # actual transfers), counted in kernel_gather_bytes
        gather = (cls.use_kernels and self.mesh is not None
                  and self.mesh_k > 1)
        memo: dict = {}

        def host(x):
            hit = memo.get(id(x))
            if hit is None:
                # the memo entry pins x so its id cannot be recycled mid-step
                hit = (x, self._kernel_gather([x])[0])
                memo[id(x)] = hit
            return hit[1]

        def rels_of(r):
            if not gather:
                return r.rels
            return [Relation(*(host(x) for x in rel)) for rel in r.rels]
        rels_b = [Relation(jnp.stack([rels_of(r)[s].keys for r in reqs]),
                           jnp.stack([rels_of(r)[s].values for r in reqs]),
                           jnp.stack([rels_of(r)[s].valid for r in reqs]))
                  for s in range(cls.n_inputs)]
        seeds = jnp.asarray([r.seed for r in reqs], jnp.uint32)
        fseeds = jnp.asarray([r.seed if r.filter_seed is None
                              else r.filter_seed for r in reqs], jnp.uint32)
        num_blocks = bloom.num_blocks_for(max(cls.caps), cls.fp_rate)
        # words are fetched per REAL request only (pad slots replay the last
        # request's words) so the build/reuse counters stay honest; a
        # streaming request carries its window's pre-merged words instead
        per_req = []
        for r in batch:
            if r._words is not None:
                assert len(r._words) == cls.n_inputs, r
                ws = list(r._words)
            else:
                fs = r.seed if r.filter_seed is None else r.filter_seed
                ws = [self._words_for(r.rels[s], r._fps[s], num_blocks, fs,
                                      use_kernels=cls.use_kernels)
                      for s in range(cls.n_inputs)]
            if gather:  # replicated mesh words -> default device, metered
                # per side, pre-stack: cached word arrays are shared across
                # slots of one dataset, so each moves at most once per step
                ws = [host(x) for x in ws]
            per_req.append(jnp.stack(ws))
        words_b = jnp.stack(per_req + [per_req[-1]] * (B - len(batch)))
        return B, rels_b, words_b, seeds, fseeds, num_blocks

    def _decide_b_rows(self, cls: ShapeClass, batch, B, population, skeys,
                       strata_slice, d_filter):
        """Host decisions: exact-affordable?  b_i from budget + sigma.

        The strata layout is whatever the prepare stage emitted — canonical
        [S] for exact-parity, concatenated per-device [k*S] for psum; both
        are complete disjoint covers of the strata, and every decision here
        is per-stratum, so the same code sizes both.
        """
        sampled_idx, b_rows = [], []
        zeros_b = jnp.zeros((population.shape[1],), jnp.float32)
        for i, req in enumerate(batch):
            budget, total_pop = req.budget, float(population[i].sum())
            exact_ok = budget.is_exact or (
                budget.latency_s is not None and self.cost_model is not None
                and float(self.cost_model.beta_compute) * total_pop
                + self.cost_model.epsilon + d_filter <= budget.latency_s
                and budget.error is None)
            if exact_ok:
                b_rows.append(zeros_b)
                continue
            sigma = None
            if budget.error is not None and self.sigma.has(req.query_id):
                sigma = self.sigma.lookup(req.query_id, skeys[i])
            b_rows.append(decide_sample_sizes(
                budget, strata_slice(i), self.cost_model, d_filter, sigma,
                budget.confidence))
            sampled_idx.append(i)
        exact_idx = [i for i in range(len(batch)) if i not in sampled_idx]
        b_rows += [zeros_b] * (B - len(batch))
        return sampled_idx, exact_idx, b_rows

    def _finish_batch(self, batch, *, strata_slice, live_counts, total_counts,
                      fbytes, d_filter, exact_idx, e_est, e_cnt,
                      value, err, cnt, dof, stats, skeys, dropped=None):
        """Per-query results + sigma feedback (shared by both backends)."""
        n = batch[0]._class.n_inputs
        for i, req in enumerate(batch):
            strata_i = strata_slice(i)
            live_i, tot_i = live_counts[i], total_counts[i]
            diag = dict(
                dist_dropped_tuples=0.0 if dropped is None
                else float(dropped[i]),
                total_counts=tot_i, live_counts=live_i,
                overlap_fraction=jnp.sum(live_i)
                / jnp.maximum(jnp.sum(tot_i), 1),
                filter_bytes=fbytes,
                shuffled_bytes_filtered=jnp.sum(live_i) * TUPLE_BYTES
                + filter_exchange_bytes(n, fbytes),
                shuffled_bytes_repartition=jnp.sum(tot_i) * TUPLE_BYTES,
                num_strata=strata_i.num_strata,
                strata_overflow=strata_i.overflow,
                total_population=jnp.sum(strata_i.population),
                d_filter_s=d_filter)
            if i in exact_idx:
                req.result = JoinResult(
                    e_est[i], jnp.zeros(()), e_cnt[i], jnp.zeros(()),
                    JoinDiagnostics(sample_draws=jnp.zeros(()), sampled=False,
                                    **diag),
                    strata=strata_i)
                self.diagnostics.exact_queries += 1
                continue
            stats_i = jax.tree_util.tree_map(lambda x: x[i], stats)
            req.result = JoinResult(
                value[i], err[i], cnt[i], dof[i],
                JoinDiagnostics(sample_draws=jnp.sum(stats_i.n_sampled),
                                sampled=True, **diag),
                stats=stats_i, strata=strata_i)
            sig = np.asarray(jax.device_get(measured_sigma(stats_i)))
            ok = np.asarray(jax.device_get(
                stats_i.valid & (stats_i.n_sampled > 1)))
            self.sigma.update(req.query_id, skeys[i], sig, ok)
            self.diagnostics.sampled_queries += 1

    def _stage_builders(self, cls: ShapeClass, num_blocks: int):
        """Per-backend stage builders + dispatch-argument adapters.

        The single-device, kernel and mesh paths share every other line of
        the step (warmup, timing, host decisions, result assembly); only the
        compiled stage programs and two extra sample/exact arguments differ.
        """
        if cls.use_kernels:
            # the fused Pallas sampler is two-way/non-dedup (the paper's hot
            # case); other kernel classes keep the kernel-backed prepare and
            # fall back to the vmapped jnp sampler — exactly approx_join's
            # own use_kernels composition, so bit-parity holds either way
            if cls.n_inputs == 2 and not cls.dedup:
                sample = partial(_make_sample_kernels, cls.b_max, cls.agg,
                                 cls.confidence, cls.expr)
            else:
                sample = partial(_make_sample, cls.b_max, cls.agg, cls.dedup,
                                 cls.confidence, cls.expr)
            return dict(
                prepare=partial(_make_prepare_kernels, cls.max_strata),
                sample=sample,
                exact=partial(_make_exact, cls.agg, cls.expr),
                sample_args=lambda prep, b, s: (prep.sorted_rels, prep.strata,
                                                b, s),
                exact_args=lambda prep: (prep.sorted_rels, prep.strata))
        if self.mesh is None:
            return dict(
                prepare=partial(_make_prepare, cls.max_strata),
                sample=partial(_make_sample, cls.b_max, cls.agg, cls.dedup,
                               cls.confidence, cls.expr),
                exact=partial(_make_exact, cls.agg, cls.expr),
                sample_args=lambda prep, b, s: (prep.sorted_rels, prep.strata,
                                                b, s),
                exact_args=lambda prep: (prep.sorted_rels, prep.strata))
        cap = cls.bucket_cap or max(cls.caps) // self.mesh_k
        if cls.serve_mode == "psum":
            return dict(
                prepare=partial(make_serve_prepare, self.mesh,
                                self.join_axes, n_rels=cls.n_inputs,
                                num_blocks=num_blocks,
                                max_strata=cls.max_strata, bucket_cap=cap,
                                merge="psum"),
                sample=partial(make_serve_sample_psum, self.mesh,
                               self.join_axes, n_rels=cls.n_inputs,
                               b_max=cls.b_max, agg=cls.agg, dedup=cls.dedup,
                               confidence=cls.confidence, expr=cls.expr),
                exact=partial(make_serve_exact_psum, self.mesh,
                              self.join_axes, n_rels=cls.n_inputs,
                              agg=cls.agg, expr=cls.expr),
                sample_args=lambda prep, b, s: (prep.sorted_rels,
                                                prep.local_strata, b, s),
                exact_args=lambda prep: (prep.sorted_rels,
                                         prep.local_strata))
        return dict(
            prepare=partial(make_serve_prepare, self.mesh, self.join_axes,
                            n_rels=cls.n_inputs, num_blocks=num_blocks,
                            max_strata=cls.max_strata, bucket_cap=cap),
            sample=partial(make_serve_sample, self.mesh, self.join_axes,
                           n_rels=cls.n_inputs, b_max=cls.b_max, agg=cls.agg,
                           dedup=cls.dedup, confidence=cls.confidence,
                           expr=cls.expr),
            exact=partial(make_serve_exact, self.mesh, self.join_axes,
                          n_rels=cls.n_inputs, agg=cls.agg, expr=cls.expr),
            sample_args=lambda prep, b, s: (prep.sorted_rels,
                                            prep.local_strata,
                                            prep.strata.keys,
                                            prep.strata.valid, b, s),
            exact_args=lambda prep: (prep.sorted_rels, prep.local_strata,
                                     prep.strata))

    def _wire_bytes_model(self, cls: ShapeClass) -> float:
        """Static per-device collective bytes for ONE query through the mesh
        pipeline (buffers, not live tuples — what a static-shape dataflow
        puts on the wire; the serve-time restatement of Eq. 24)."""
        k = self.mesh_k
        if k <= 1:
            return 0.0
        cap = cls.bucket_cap or max(cls.caps) // k
        n = cls.n_inputs
        a2a = n * (k - 1) * cap * TUPLE_BYTES     # key shuffle send buffers
        if cls.serve_mode == "psum":
            merge = len(SumParts._fields) * 4 * (k - 1)
        else:
            # gather merge: all_gathers of [S] slot arrays — strata keys +
            # per-side counts (prepare), 7 stat fields (sample), per-side
            # sums (exact)
            merge = ((1 + n) + 7 + n) * cls.max_strata * 4 * (k - 1)
        return float(a2a + merge)

    def _run_batch(self, cls: ShapeClass, batch: list[JoinRequest]) -> None:
        """One engine step — single fused dispatch per stage; with a mesh,
        each dispatch spans all devices through the shard_map pipeline.

        Traced, the step is tiled by spans in order: ``inputs`` (stacking,
        stage builders, executable lookup), ``compile`` (a fresh shape
        class only), ``prepare``, ``decide`` (strata fetch, sample sizes),
        ``sample``/``exact``, ``tracer`` (its own reconciliation records)
        and ``finish`` (result assembly, sigma feedback, meters, and
        releasing the step's arrays on the way out)."""
        # stage-timing scratch for the tracer ({} only while tracing, so the
        # untraced path keeps its exact laziness — no extra blocking, and no
        # clock read it does not need)
        stages = {} if self.tracer.enabled else None
        t_in = time.perf_counter() if stages is not None else 0.0
        B, rels_b, words_b, seeds, fseeds, num_blocks = \
            self._batch_inputs(cls, batch)
        builders = self._stage_builders(cls, num_blocks)

        prepare, fresh = self._executable("prepare", cls, B,
                                          builders["prepare"])
        if stages is not None:
            stages["inputs"] = (t_in, time.perf_counter() - t_in, {})
        if fresh:
            # warm the executable off the clock: d_filter feeds the latency
            # cost function (§3.2), which models repeated query execution —
            # charging one-off trace+compile seconds would zero out every
            # latency budget on the first batch of a shape class.
            tc = time.perf_counter()
            jax.block_until_ready(
                prepare(rels_b, words_b, fseeds).strata.counts)
            if stages is not None:
                stages["compile"] = (tc, time.perf_counter() - tc,
                                     {"stage": "prepare"})
        t0 = time.perf_counter()
        prep = prepare(rels_b, words_b, fseeds)
        jax.block_until_ready(prep.strata.counts)
        t_prep = time.perf_counter()
        d_filter = t_prep - t0
        self.diagnostics.filter_s += d_filter
        if stages is not None:
            stages["prepare"] = (t0, d_filter, {})

        population = np.asarray(jax.device_get(prep.population))
        skeys = np.asarray(jax.device_get(prep.strata.keys))

        def slice_i(i):
            return jax.tree_util.tree_map(lambda x: x[i], prep.strata)

        sampled_idx, exact_idx, b_rows = self._decide_b_rows(
            cls, batch, B, population, skeys, slice_i, d_filter)

        # -- fused device dispatches (per stage, whole batch) ---------------
        # (``decide`` runs from prepare's wait to the first of them)
        value = err = cnt = dof = stats = e_est = e_cnt = None
        if sampled_idx:
            sample, _ = self._executable("sample", cls, B,
                                         builders["sample"])
            ts = time.perf_counter()
            value, err, cnt, dof, stats = sample(*builders["sample_args"](
                prep, jnp.stack(b_rows), seeds + jnp.uint32(1)))
            if stages is not None:
                stages.setdefault("decide", (t_prep, ts - t_prep, {}))
                jax.block_until_ready(value)
                stages["sample"] = (ts, time.perf_counter() - ts,
                                    {"queries": len(sampled_idx)})
        if exact_idx:
            exact, _ = self._executable("exact", cls, B, builders["exact"])
            ts = time.perf_counter()
            e_est, e_cnt = exact(*builders["exact_args"](prep))
            if stages is not None:
                stages.setdefault("decide", (t_prep, ts - t_prep, {}))
                jax.block_until_ready(e_est)
                stages["exact"] = (ts, time.perf_counter() - ts,
                                   {"queries": len(exact_idx)})

        # kernel classes run the single-device pipeline even on a mesh
        # server (plain PrepareOut: no shuffle buckets, nothing dropped)
        meshless = self.mesh is None or cls.use_kernels
        fbytes = num_blocks * bloom.WORDS_PER_BLOCK * 4
        if stages is not None:
            t_rec = time.perf_counter()
            self._recon_batch = self._recon_records(cls, batch, prep,
                                                    fbytes, meshless)
            t_fin = time.perf_counter()
            stages["tracer"] = (t_rec, t_fin - t_rec, {})
            # finish runs to the step's end, which step() stamps
            stages["finish"] = (t_fin, 0.0, {})
            self._stage_trace = stages
        if cls.use_kernels:
            self.diagnostics.kernel_queries += len(batch)
        dropped = None if meshless else np.asarray(
            jax.device_get(prep.bucket_overflow), np.float64)
        self._finish_batch(
            batch, strata_slice=slice_i, live_counts=prep.live_counts,
            total_counts=prep.total_counts, fbytes=fbytes, d_filter=d_filter,
            exact_idx=exact_idx, e_est=e_est, e_cnt=e_cnt, value=value,
            err=err, cnt=cnt, dof=dof, stats=stats, skeys=skeys,
            dropped=dropped)

        self.diagnostics.filter_exchange_bytes_model += \
            len(batch) * float(filter_exchange_bytes(cls.n_inputs, fbytes))
        if not meshless:
            # measured per-device shuffle volume (the paper's data-movement
            # reduction, observable from the server); pad slots excluded
            n_real = len(batch)
            self.diagnostics.dist_shuffled_tuple_bytes += float(
                np.asarray(jax.device_get(
                    prep.shuffled_tuple_bytes))[:n_real].sum())
            self.diagnostics.per_device_shuffled_bytes += np.asarray(
                jax.device_get(prep.device_shuffled_bytes))[:n_real].sum(
                    axis=0)
            # capacity-plan feedback: rows dropped beyond the bucket plan
            # (always 0 under the lossless exact-parity default)
            self.diagnostics.dist_dropped_tuples += float(
                dropped[:n_real].sum())
            self.diagnostics.per_device_dropped_tuples += np.asarray(
                jax.device_get(prep.device_dropped),
                np.float64)[:n_real].sum(axis=0)
            self.diagnostics.dist_wire_bytes_model += \
                n_real * self._wire_bytes_model(cls)

    def _recon_records(self, cls: ShapeClass, batch: list[JoinRequest],
                       prep, fbytes: int, meshless: bool) -> tuple:
        """Per-query byte-reconciliation records (traced steps only): each
        modeled cost paired with its metered counterpart, keyed by request
        identity for ``_trace_step``; returned with the per-query filter
        exchange model.  The meters stay device arrays: each record's
        ``meter`` reads them when a report is built (``resolve_recon``), so
        a traced step waits on the device no more than an untraced one."""
        n, k = cls.n_inputs, self.mesh_k
        meters = (prep.live_counts, None, None) if meshless else (
            prep.live_counts, prep.shuffled_tuple_bytes,
            prep.device_shuffled_bytes)
        # one host read per step, shared by the step's records
        fetch = cache(partial(jax.device_get, meters))
        path, wire = self._path_of(cls), self._wire_bytes_model(cls)
        fe_model = float(filter_exchange_bytes(n, fbytes))
        out = {}
        for i, req in enumerate(batch):
            out[id(req)] = {
                "query_id": req.query_id, "path": path,
                "stream": req.stream, "window_id": req.window_id,
                "plan": req.plan, "plan_node": req.plan_node,
                "meter": partial(_metered_pairs, fetch, i, fe_model, wire, k,
                                 req._bytes_model)}
        return fe_model, out

    def reconciliation_report(self) -> dict:
        """Modeled-vs-metered byte report: per-query records (traced
        queries), per-path aggregates, and the cumulative server-level
        pairs that exist with tracing off too."""
        d = self.diagnostics
        server_pairs = [
            recon_pair("filter_exchange_bytes", d.filter_exchange_bytes_model,
                       d.filter_exchange_bytes_measured
                       if self.mesh is not None else None),
            recon_pair("dist_wire_bytes_model", d.dist_wire_bytes_model,
                       d.dist_shuffled_tuple_bytes
                       if self.mesh is not None else None),
            # host gathers of the kernel-on-mesh route are unmodeled cost:
            # modeled 0, so any metered bytes surface as pure model error
            recon_pair("kernel_gather_bytes", 0.0,
                       d.kernel_gather_bytes or None),
        ]
        return _recon_report(self.tracer.recon, server_pairs)

    def query_trace(self, query_id: str) -> list:
        """Span forest of every traced execution of ``query_id`` (each
        request instance roots its own ``query`` span)."""
        return span_tree(e for e in self.tracer.events
                         if e["args"].get("query_id") == query_id)
