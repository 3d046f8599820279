"""JoinServer serving path: bit-identity with direct approx_join, executable
cache behaviour, tenant isolation of the sigma feedback, shape classes."""

import numpy as np
import pytest

from conftest import make_pair
from repro.core.budget import QueryBudget
from repro.core.cost import SigmaRegistry
from repro.core.join import approx_join
from repro.core.relation import bucket_capacity, bucket_to_pow2, relation
from repro.runtime.join_serve import (JoinRequest, JoinServer,
                                      ServerDiagnostics, shape_class_of)

MS, BM = 1024, 512   # max_strata / b_max used throughout


def _identical(a, b):
    """Bitwise equality of the user-facing result surface."""
    return (float(a.estimate) == float(b.estimate)
            and float(a.error_bound) == float(b.error_bound)
            and float(a.count) == float(b.count)
            and float(a.dof) == float(b.dof))


def _req(rels, budget, qid, seed):
    return JoinRequest(rels=rels, budget=budget, query_id=qid, seed=seed,
                       max_strata=MS, b_max=BM)


def test_single_query_bit_identical_to_direct(rng):
    r1, r2 = make_pair(rng, n=1 << 12)      # pow2: bucketing is a no-op
    srv = JoinServer(batch_slots=4)
    q = srv.submit(_req([r1, r2], QueryBudget(error=0.5), "t0", seed=5))
    srv.run()
    direct = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=MS,
                         b_max=BM, seed=5)
    assert q.done and _identical(q.result, direct)
    assert bool(q.result.diagnostics.sampled)
    # live/total counts and population survive the batched path bit-exactly
    np.testing.assert_array_equal(
        np.asarray(q.result.diagnostics.live_counts),
        np.asarray(direct.diagnostics.live_counts))
    np.testing.assert_array_equal(np.asarray(q.result.strata.keys),
                                  np.asarray(direct.strata.keys))


def test_batched_mixed_budgets_bit_identical(rng):
    """One engine step serves a mixed exact/sampled batch; every slot is
    bit-identical to its own direct approx_join call."""
    pairs = [make_pair(rng, n=1 << 12),
             make_pair(rng, n=1 << 12, keys2=(450, 950)),
             make_pair(rng, n=1 << 12, mu1=3.0)]
    budgets = [QueryBudget(error=0.5), QueryBudget(error=0.5), QueryBudget()]
    srv = JoinServer(batch_slots=4)
    qs = [srv.submit(_req(list(p), b, f"t{i}", seed=10 + i))
          for i, (p, b) in enumerate(zip(pairs, budgets))]
    assert srv.step() == 3                   # one batch, same shape class
    for i, (p, b) in enumerate(zip(pairs, budgets)):
        direct = approx_join(list(p), b, max_strata=MS, b_max=BM,
                             seed=10 + i)
        assert _identical(qs[i].result, direct), i
    assert not bool(qs[2].result.diagnostics.sampled)  # exact budget


def test_cache_hits_increase_on_repeat_shape_class(rng):
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=2)
    srv.submit(_req([r1, r2], QueryBudget(error=0.5), "a", seed=1))
    srv.run()
    first = srv.diagnostics.snapshot()
    # >=3 executables compiled (filter build, prepare, sample); the only
    # admissible hit so far is the second relation reusing the build exe
    assert first["compiles"] >= 3 and first["cache_hits"] <= 1
    srv.submit(_req([r1, r2], QueryBudget(error=0.5), "a", seed=2))
    srv.run()
    second = srv.diagnostics.snapshot()
    assert second["compiles"] == first["compiles"]     # zero recompiles
    assert second["cache_hits"] > first["cache_hits"]
    # a new shape class compiles fresh executables
    r3, r4 = make_pair(rng, n=1 << 12)
    srv.submit(_req([r3, r4], QueryBudget(error=0.5), "a", seed=3))
    srv.run()
    assert srv.diagnostics.compiles > second["compiles"]


def test_interleaved_tenants_do_not_cross_contaminate_sigma(rng):
    """Tenant A and B interleave in the queue; each query_id's sigma table
    matches the one a dedicated per-tenant driver would have produced."""
    ra = make_pair(rng, n=1 << 12)
    rb = make_pair(rng, n=1 << 12, keys2=(300, 800), mu1=20.0)
    srv = JoinServer(batch_slots=2)
    for q in range(2):
        srv.submit(_req(list(ra), QueryBudget(error=0.5), "tenantA", q))
        srv.submit(_req(list(rb), QueryBudget(error=0.5), "tenantB", q))
    srv.run()
    assert set(srv.sigma.table) == {"tenantA", "tenantB"}

    for qid, rels in (("tenantA", ra), ("tenantB", rb)):
        reg = SigmaRegistry()
        for q in range(2):
            approx_join(list(rels), QueryBudget(error=0.5), max_strata=MS,
                        b_max=BM, seed=q, sigma_registry=reg, query_id=qid)
        assert srv.sigma.table[qid] == reg.table[qid], qid


def test_two_shape_classes_concurrently(rng):
    """Queries from two capacity shape classes interleave; the engine groups
    them into per-class batches and each result stays bit-identical.

    Each query gets a unique query_id: same-id queries co-batched into one
    step legitimately diverge from a *sequential* direct driver, because
    sigma feedback lands between steps, not between slots of one step.
    """
    small = make_pair(rng, n=1 << 11)
    large = make_pair(rng, n=1 << 12)
    srv = JoinServer(batch_slots=4)
    qs = []
    for q in range(2):
        qs.append((small, srv.submit(
            _req(list(small), QueryBudget(error=0.5), f"s{q}", seed=q))))
        qs.append((large, srv.submit(
            _req(list(large), QueryBudget(error=0.5), f"l{q}", seed=q))))
    srv.run()
    classes = {shape_class_of(r) for _, r in qs}
    assert len(classes) == 2
    for rels, req in qs:
        direct = approx_join(list(rels), QueryBudget(error=0.5),
                             max_strata=MS, b_max=BM, seed=req.seed)
        assert _identical(req.result, direct)
    assert srv.diagnostics.steps <= 4        # batched, not one step/query


def test_nonpow2_input_bucketed_like_direct_padded_call(rng):
    """Non-pow2 capacities are padded to their bucket; the result equals a
    direct approx_join on the explicitly bucketed relations."""
    n = 3000                                  # buckets to 4096
    r1 = relation(rng.integers(0, 500, n).astype(np.uint32),
                  rng.normal(10, 2, n).astype(np.float32))
    r2 = relation(rng.integers(400, 900, n).astype(np.uint32),
                  rng.normal(5, 1, n).astype(np.float32))
    assert bucket_capacity(n) == 4096
    srv = JoinServer(batch_slots=2)
    q = srv.submit(_req([r1, r2], QueryBudget(error=0.5), "t", seed=3))
    srv.run()
    direct = approx_join([bucket_to_pow2(r1), bucket_to_pow2(r2)],
                         QueryBudget(error=0.5), max_strata=MS, b_max=BM,
                         seed=3)
    assert _identical(q.result, direct)


def test_dataset_handles_and_validation(rng):
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=2)
    srv.register_dataset("shared", [r1, r2])
    q = srv.submit(JoinRequest(dataset="shared", budget=QueryBudget(),
                               query_id="t", max_strata=MS, b_max=BM))
    srv.run()
    direct = approx_join([r1, r2], QueryBudget(), max_strata=MS, b_max=BM)
    assert _identical(q.result, direct)
    assert q.queue_latency_s > 0
    with pytest.raises(ValueError):
        srv.submit(JoinRequest(budget=QueryBudget()))        # no rels
    with pytest.raises(ValueError):
        srv.submit(JoinRequest(rels=[r1, r2], agg="median"))  # unknown agg


def test_dataset_filter_words_built_once(rng):
    """Registered-dataset Bloom filter reuse: N steps over a dataset build
    the filter words exactly once per (num_blocks, seed); re-registering
    identical relations under a new name reuses the cache; a new seed (the
    filter hash is seeded) builds fresh words."""
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=1)        # force one step per query
    srv.register_dataset("ds", [r1, r2])

    def submit(name, qid, seed):
        return srv.submit(JoinRequest(dataset=name,
                                      budget=QueryBudget(error=0.5),
                                      query_id=qid, seed=seed, max_strata=MS,
                                      b_max=BM))

    q = submit("ds", "t0", 7)
    for i in range(1, 3):
        submit("ds", f"t{i}", 7)
    srv.run()
    d = srv.diagnostics
    assert d.steps == 3
    assert d.filter_builds == 2            # one per relation, built once
    assert d.filter_cache_hits == 4        # 2 later steps x 2 relations
    # the cached-words path is still bit-identical to a direct driver call
    direct = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=MS,
                         b_max=BM, seed=7)
    assert _identical(q.result, direct)

    # re-register the same relations under a new name: same fingerprints
    srv.register_dataset("ds-again", [r1, r2])
    submit("ds-again", "t3", 7)
    srv.run()
    assert srv.diagnostics.filter_builds == 2
    assert srv.diagnostics.filter_cache_hits == 6

    # a different seed hashes differently -> fresh words, once
    submit("ds", "t4", 8)
    srv.run()
    assert srv.diagnostics.filter_builds == 4


def test_sigma_pipeline_matches_sequential_driver(rng):
    """Cross-step sigma pipelining: same-query_id error-budget repeats
    submitted together are deferred one step each, so every repeat sees the
    previous execution's measured sigma — bit-identical to a sequential
    driver threading feedback through one registry."""
    r1, r2 = make_pair(rng, n=1 << 12)
    srv = JoinServer(batch_slots=4)
    qs = [srv.submit(_req([r1, r2], QueryBudget(error=0.5), "tenant", seed=s))
          for s in range(3)]
    srv.run()
    assert srv.diagnostics.steps == 3           # one repeat per step
    assert srv.diagnostics.sigma_deferrals == 3
    reg = SigmaRegistry()
    for s in range(3):
        direct = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=MS,
                             b_max=BM, seed=s, sigma_registry=reg,
                             query_id="tenant")
        assert _identical(qs[s].result, direct), s


def test_sigma_pipeline_fills_slots_with_other_tenants(rng):
    """Deferred repeats must not cost throughput when the queue has id
    diversity: alternating tenants keep every batch full, so N rounds of two
    tenants take exactly N steps — same as without pipelining."""
    r1, r2 = make_pair(rng, n=1 << 12)
    srv = JoinServer(batch_slots=2)
    for q in range(3):
        srv.submit(_req([r1, r2], QueryBudget(error=0.5), "A", seed=q))
        srv.submit(_req([r1, r2], QueryBudget(error=0.5), "B", seed=q))
    srv.run()
    assert srv.diagnostics.steps == 3
    assert srv.diagnostics.max_batch == 2

    # opting out restores co-batching: all three same-id repeats in one step
    srv2 = JoinServer(batch_slots=4, sigma_pipeline=False)
    for q in range(3):
        srv2.submit(_req([r1, r2], QueryBudget(error=0.5), "A", seed=q))
    srv2.run()
    assert srv2.diagnostics.steps == 1
    assert srv2.diagnostics.sigma_deferrals == 0


def test_queue_latency_percentiles(rng):
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=2)
    qs = [srv.submit(_req([r1, r2], QueryBudget(error=0.5), f"t{q}", seed=q))
          for q in range(4)]
    srv.run()
    snap = srv.diagnostics.snapshot()
    assert "queue_latencies" not in snap        # raw ring stays internal
    assert 0 < snap["queue_latency_p50_s"] <= snap["queue_latency_p95_s"] \
        <= snap["queue_latency_max_s"]
    assert snap["queue_latency_max_s"] == \
        pytest.approx(max(q.queue_latency_s for q in qs))


def test_latency_percentiles_empty_and_single_sample():
    d = ServerDiagnostics()
    snap = d.snapshot()                        # empty rings -> hard zeros
    for k in ("queue_latency_p50_s", "queue_latency_p95_s",
              "queue_latency_max_s", "e2e_latency_p50_s",
              "e2e_latency_p95_s", "e2e_latency_max_s"):
        assert snap[k] == 0.0
    assert snap["per_tenant"] == {}
    d.note_latency("a", 0.25, 0.5, 8)          # one sample: p50 == p95 == max
    snap = d.snapshot()
    assert snap["queue_latency_p50_s"] == snap["queue_latency_p95_s"] \
        == snap["queue_latency_max_s"] == 0.25
    assert snap["e2e_latency_p95_s"] == 0.5
    assert snap["per_tenant"]["a"]["samples"] == 1
    assert snap["per_tenant"]["a"]["queue_latency_p95_s"] == 0.25


def test_latency_percentiles_ring_wrap_and_reset():
    """The sample rings are bounded: with cap=4, eight samples 0..7 leave
    exactly the last four, and the percentiles describe those — while the
    cumulative sums keep covering every query ever served."""
    d = ServerDiagnostics()
    for i in range(8):
        d.note_latency("t", float(i), float(i), 4)
    assert d.queue_latencies == [4.0, 5.0, 6.0, 7.0]
    assert d.tenant_latencies["t"][0] == [4.0, 5.0, 6.0, 7.0]
    snap = d.snapshot()
    assert snap["queue_latency_max_s"] == 7.0
    assert snap["queue_latency_p50_s"] == pytest.approx(5.5)
    assert snap["queue_latency_p95_s"] == pytest.approx(6.85)
    assert d.queue_latency_s == sum(range(8))  # cumulative: unwindowed
    d.reset_latencies()
    assert d.queue_latencies == [] and d.e2e_latencies == []
    assert d.tenant_latencies == {}
    assert d.queue_latency_s == sum(range(8))  # sums survive a ring reset
    assert d.snapshot()["queue_latency_p95_s"] == 0.0


def test_latency_ring_bounded_by_server_cap(rng):
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=2, latency_samples=2)
    for q in range(5):
        srv.submit(_req([r1, r2], QueryBudget(error=0.5), "t/a", seed=q))
        srv.run()
    d = srv.diagnostics
    assert d.queries == 5
    assert len(d.queue_latencies) == 2 and len(d.e2e_latencies) == 2
    assert len(d.tenant_latencies["t"][0]) == 2
    assert d.snapshot()["per_tenant"]["t"]["samples"] == 2


def test_kernel_batch_mixed_seeds_bit_identical_to_per_query(rng):
    """The acceptance contract: ONE engine step serves a mixed-seed kernel
    batch through the stacked Pallas grids, and every slot is bit-identical
    to its own per-query approx_join(use_kernels=True) call."""
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=4)
    seeds = [3, 11, 3, 250]
    qs = [srv.submit(JoinRequest(rels=[r1, r2], budget=QueryBudget(error=0.5),
                                 query_id=f"t{i}", seed=s, max_strata=512,
                                 b_max=256, use_kernels=True))
          for i, s in enumerate(seeds)]
    assert srv.step() == 4                    # one fused dispatch, no loop
    for i, s in enumerate(seeds):
        direct = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=512,
                             b_max=256, seed=s, use_kernels=True)
        assert _identical(qs[i].result, direct), (i, s)
        assert bool(qs[i].result.diagnostics.sampled)
    assert srv.diagnostics.kernel_queries == 4
    assert srv.diagnostics.max_batch == 4
    # meshless: the batched kernel path never round-trips rows to the host
    assert srv.diagnostics.kernel_gather_bytes == 0.0


def test_kernel_seed_sweep_no_recompiles_no_rebuilds(rng):
    """The static-seed recompile bug, fixed at the engine: a 16-seed warm
    sweep over one kernel shape class (mixed batch fills too) must keep the
    compile AND filter-build counters flat — seeds are runtime operands and
    the dataset words cache ignores the sampling seed entirely."""
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=4)
    srv.register_dataset("ds", [r1, r2])

    def submit(q, seed):
        return srv.submit(JoinRequest(
            dataset="ds", budget=QueryBudget(error=0.5), query_id=f"t{q}",
            seed=seed, filter_seed=7, max_strata=512, b_max=256,
            use_kernels=True))

    # warmup: cover the batch fills the sweep uses (4-wide and 2-wide)
    for q in range(4):
        submit(q, seed=1000 + q)
    srv.run()
    for q in range(2):
        submit(q, seed=2000 + q)
    srv.run()
    warm = srv.diagnostics.snapshot()
    assert warm["filter_builds"] == 2          # one per relation, ever

    qs = []
    for seed in range(16):                     # 4 full batches + 2-fills
        qs.append(submit(seed % 4, seed))
        if seed % 4 == 3:
            srv.run()
    for seed in range(16, 20, 2):
        submit(0, seed), submit(1, seed + 1)
        srv.run()
    after = srv.diagnostics.snapshot()
    assert after["compiles"] == warm["compiles"], "seed sweep recompiled"
    assert after["filter_builds"] == warm["filter_builds"], \
        "seed sweep rebuilt filter words"
    assert all(q.done for q in qs)


def test_kernel_route_accepts_filter_seed_and_prebuilt_words(rng):
    """filter_seed decoupling (and prebuilt words) now work on the kernel
    path — the refactor lifted the old ValueError — and stay bit-identical
    to the jnp path under the same (filter_seed, seed) split."""
    from repro.core import bloom
    r1, r2 = make_pair(rng, n=1 << 11)
    srv = JoinServer(batch_slots=2)

    def submit(use_kernels, **kw):
        return srv.submit(JoinRequest(
            rels=[r1, r2], budget=QueryBudget(error=0.5), seed=3,
            max_strata=512, b_max=256, use_kernels=use_kernels, **kw))

    a = submit(True, query_id="k", filter_seed=9)
    b = submit(False, query_id="j", filter_seed=9)
    srv.run()
    assert _identical(a.result, b.result)

    nb = bloom.num_blocks_for(1 << 11, 0.01)
    words = [bloom.build(r.keys, r.valid, nb, 9).words for r in (r1, r2)]
    c = submit(True, query_id="kw")
    c.filter_seed = 9
    c._words = words
    d = submit(True, query_id="kw2", filter_seed=9)
    srv.run()
    assert _identical(c.result, d.result)      # prebuilt == cache-built


def test_kernel_route_on_mesh1_no_host_gather(rng):
    """A 1-device mesh server serves kernel queries without any host
    round-trip (the rows already sit on the one device) — the satellite
    meter must read zero, and results match the meshless kernel server."""
    import jax
    import numpy as np_
    from jax.sharding import Mesh
    r1, r2 = make_pair(rng, n=1 << 11)
    mesh = Mesh(np_.array(jax.devices()[:1]), ("data",))
    srv = JoinServer(batch_slots=2, mesh=mesh)
    srv.register_dataset("ds", [r1, r2])
    q = srv.submit(JoinRequest(dataset="ds", budget=QueryBudget(error=0.5),
                               query_id="t", seed=3, max_strata=512,
                               b_max=256, use_kernels=True))
    srv.run()
    direct = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=512,
                         b_max=256, seed=3, use_kernels=True)
    assert _identical(q.result, direct)
    assert srv.diagnostics.kernel_queries == 1
    assert srv.diagnostics.kernel_gather_bytes == 0.0
