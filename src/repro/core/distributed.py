"""Distributed ApproxJoin over a JAX device mesh (shard_map).

This is the paper's Spark dataflow (Fig. 7) mapped onto SPMD collectives
(DESIGN.md §2):

  stage                     Spark                       here
  ------------------------- --------------------------- ----------------------
  partition filters          Map at each worker          local bloom.build
  dataset filter             treeReduce OR to driver     all_gather + OR fold
                                                         (hierarchical: intra-
                                                         pod first, then pods)
  join filter + broadcast    driver AND + broadcast      local AND (replicated)
  probe + discard            filter() on workers         local probe -> mask
  cogroup shuffle            hash shuffle                bucketize + all_to_all
  sampleDuringJoin           per-key edge sampling       vectorized sampler
  merge partial results      collect at driver           gather + key-sort, or
                                                         psum of SumParts

The pipeline is factored into per-stage functions mirroring
``core/join.py``'s ``prepare/exact/sample/estimate`` split, so the serving
engine (``runtime/join_serve.py``) can cache per-stage executables for the
distributed path exactly as it does for the single-device path.

Two merge strategies:

* ``merge='gather'`` (default): per-device strata/stats are all_gathered,
  key-sorted into the canonical single-device ``[S]`` slot layout, and
  finished with the *same* arithmetic as ``core/join.py`` — results are
  **bit-identical** to the single-device pipeline at any mesh size (the
  shuffle routes every key to exactly one device, the received rows arrive in
  source-major = original-row order, and the sampler keys its PRNG on the
  join key, so every per-stratum quantity is reproduced exactly; asserted in
  ``tests/test_join_serve_distributed.py``).

* ``merge='psum'``: the paper's dataflow — per-device estimator parts ADD
  across devices (strata are device-complete after the shuffle) and the merge
  is a single psum.  Cheapest collectives (used by the cluster-scale
  roofline dry-runs); results agree with single-device up to float
  reassociation.

Everything is static-shape: the shuffle uses capacity-bounded buckets
(overflow is counted and surfaced — the feedback path for elastic re-runs).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import bloom
from repro.core.budget import QueryBudget
from repro.core.cost import CostModel, fraction_for_latency
from repro.core.estimators import (HTParts, StratumStats, clt_avg_from,
                                   clt_count, clt_finish, clt_stdev_from,
                                   clt_sum_parts, ht_finish, ht_sum_parts,
                                   second_moment_stats, SumParts)
from repro.core.hashing import hash2, u32
from repro.core.join import (EXPRS, TUPLE_BYTES, estimate_stage,
                             exact_stage_from_sums, _pilot_sizes)
from repro.core.relation import Relation, sort_by_key
from repro.core.sampling import (SENTINEL, SampleResult, Strata, build_strata,
                                 exact_count, exact_sum_of_products,
                                 exact_sum_of_sums, per_stratum_value_sums,
                                 sample_edges)


class DistJoinResult(NamedTuple):
    estimate: jnp.ndarray
    error_bound: jnp.ndarray
    count: jnp.ndarray
    dof: jnp.ndarray
    # meters (replicated scalars)
    shuffled_tuple_bytes: jnp.ndarray   # live tuples that crossed devices
    filter_bytes: jnp.ndarray           # filter all_gather volume (model)
    live_total: jnp.ndarray
    input_total: jnp.ndarray
    overlap_fraction: jnp.ndarray
    bucket_overflow: jnp.ndarray
    strata_overflow: jnp.ndarray
    total_population: jnp.ndarray
    sample_draws: jnp.ndarray
    device_shuffled_bytes: jnp.ndarray  # [k] per-device sent-tuple bytes
    device_dropped: jnp.ndarray         # [k] per-device bucket-dropped tuples


def planned_bucket_cap(local_rows: int, k: int, overlap: float, *,
                       slack: float = 2.0, floor: int = 8) -> int:
    """Capacity-planned shuffle bucket size from a live-fraction estimate.

    The filter's shuffle saving only reaches the wire of a static-shape
    dataflow if the all_to_all buffers shrink with it: size the per-(source,
    dest) bucket for the *expected live* rows — ``local_rows * overlap / k``
    with ``slack``x headroom — instead of the lossless worst case
    (``local_rows``).  Small buckets get a ``3 sqrt(2 mean)`` concentration
    guard instead: keys place hash-randomly but rows arrive in per-key
    clumps, so the per-bucket load is compound-Poisson with variance ~
    ``2 mean``, and a plain multiplicative slack under-provisions exactly
    when buckets are a handful of rows (at production bucket sizes the
    guard is the smaller term and the plan stays ``slack * mean``).
    Overflow beyond the plan is counted, never silent — the feedback path
    for recompile-bigger elastic re-runs.
    """
    mean = local_rows * overlap / max(k, 1)
    guard = max((slack - 1.0) * mean, 3.0 * math.sqrt(max(2.0 * mean, 0.0)))
    return max(int(mean + guard), floor)


def combined_axis_index(axes: Sequence[str]) -> jnp.ndarray:
    """Linear device index over possibly-multiple mesh axes (major first)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def or_reduce(words: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """OR-merge partition filters across the mesh (Alg. 1 reduce phase).

    Hierarchical: reduce over the innermost (fast, intra-pod ICI) axis first,
    then the outer (inter-pod DCN) axis — only one |BF| message crosses the
    slow link per pod, the treeReduce insight restated for a torus.
    """
    for a in reversed(list(axes)):
        gathered = jax.lax.all_gather(words, a)  # [k_a, nb, W]
        words = functools.reduce(jnp.bitwise_or,
                                 [gathered[i] for i in range(gathered.shape[0])])
    return words


def bucketize(rel: Relation, dest: jnp.ndarray, k: int, cap: int):
    """Scatter live rows into k capacity-bounded send buckets.

    Returns (keys [k, cap], values [k, cap], valid [k, cap], overflow []).
    Rows are ranked within their destination by sort; rows beyond ``cap`` are
    dropped and counted (static shapes; same trick as MoE capacity).
    """
    n = rel.capacity
    d = jnp.where(rel.valid, dest, k)                      # invalid -> k
    order = jnp.argsort(d)                                 # stable
    ds = d[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), ds[1:] != ds[:-1]])
    run_start = jax.lax.cummax(jnp.where(is_start, pos, 0))
    slot = pos - run_start
    ok = (ds < k) & (slot < cap)
    flat = jnp.where(ok, ds * cap + slot, k * cap)
    keys = jnp.zeros((k * cap + 1,), jnp.uint32).at[flat].set(
        rel.keys[order], mode="drop")[:-1].reshape(k, cap)
    vals = jnp.zeros((k * cap + 1,), jnp.float32).at[flat].set(
        rel.values[order], mode="drop")[:-1].reshape(k, cap)
    valid = jnp.zeros((k * cap + 1,), bool).at[flat].set(
        ok, mode="drop")[:-1].reshape(k, cap)
    overflow = jnp.sum(((ds < k) & (slot >= cap)).astype(jnp.int32))
    return keys, vals, valid, overflow


def shuffle_by_key(rel: Relation, k: int, cap: int, axes: Sequence[str],
                   seed: int):
    """Hash-partition a sharded relation so each key lands on one device.

    The received buffer is source-major and bucketize keeps original row
    order within a bucket, so for any key the received rows arrive in
    ascending original-global-row order — a stable local sort by key then
    reproduces the single-device sorted segment content exactly (the
    bit-parity invariant the gather merge relies on).
    """
    dest = (hash2(rel.keys, seed) % u32(k)).astype(jnp.int32)
    me = combined_axis_index(axes)
    sent = rel.valid & (dest != me)
    keys, vals, valid, overflow = bucketize(rel, dest, k, cap)
    # Factor the bucket dim as (size(a0), size(a1), ..., cap) and exchange
    # each factor along ITS mesh axis — the composition is the all_to_all
    # over the combined (major-first) device index.  Exchanging always on
    # the leading dim would route the later axes by SOURCE index (bug).
    sizes = [jax.lax.axis_size(a) for a in axes]
    recv = []
    for x in (keys, vals, valid):
        x = x.reshape(*sizes, cap)
        for i, a in enumerate(axes):
            x = jax.lax.all_to_all(x, a, split_axis=i, concat_axis=i,
                                   tiled=True)
        recv.append(x.reshape(-1, cap))
    out = Relation(recv[0].reshape(-1), recv[1].reshape(-1),
                   recv[2].reshape(-1))
    return out, jnp.sum(sent.astype(jnp.int32)), overflow


# ---------------------------------------------------------------------------
# Gather merge: rebuild the canonical single-device [S] slot layout from the
# per-device strata.  Every key lives on exactly one device after the
# shuffle, so sorting the gathered slots by key and truncating to S yields
# the same keys, in the same order, as a single-device build_strata — and
# any per-stratum quantity computed on the owning device drops into the
# same slot it would occupy on a single device.
# ---------------------------------------------------------------------------

def gather_concat(x: jnp.ndarray, axes: Sequence[str]) -> jnp.ndarray:
    """all_gather over possibly-multiple axes, concatenated on dim 0."""
    for a in reversed(list(axes)):
        x = jax.lax.all_gather(x, a, tiled=True)
    return x


def merge_by_key(local_keys: jnp.ndarray, fields: Sequence[jnp.ndarray],
                 axes: Sequence[str], max_strata: int):
    """Key-sort per-device [S]-leading slot arrays into canonical [S] slots.

    Returns ``(keys [S], merged_fields)``.  Slots beyond ``max_strata``
    (largest keys — the same drop rule as ``build_strata``) are truncated.
    """
    gk = gather_concat(local_keys, axes)          # [k*S]
    order = jnp.argsort(gk)                       # stable; SENTINEL slots last
    keys = gk[order][:max_strata]
    merged = [gather_concat(f, axes)[order][:max_strata] for f in fields]
    return keys, merged


def merge_strata(local: Strata, axes: Sequence[str], max_strata: int) -> Strata:
    """Merged replicated Strata in the canonical single-device layout.

    ``starts`` are zeroed — they index per-device sorted arrays and have no
    global meaning; everything downstream of the merge (host-side sample
    sizing, exact finish, estimators) only needs keys/valid/counts.
    """
    S = max_strata
    n_sides = local.counts.shape[0]
    total = jax.lax.psum(jnp.sum(local.valid.astype(jnp.int32))
                         + local.overflow, tuple(axes))
    keys, counts = merge_by_key(local.keys,
                                [local.counts[i] for i in range(n_sides)],
                                axes, S)
    valid = jnp.arange(S) < jnp.minimum(total, S)
    keys = jnp.where(valid, keys, u32(SENTINEL))
    counts = jnp.stack([jnp.where(valid, c, 0) for c in counts])
    return Strata(keys, valid, jnp.zeros_like(counts), counts,
                  jnp.maximum(total - S, 0))


def merged_to_local(merged_keys: jnp.ndarray, local_strata: Strata,
                    merged_vals: jnp.ndarray,
                    fill=0.0) -> jnp.ndarray:
    """Route a merged-[S] per-stratum array back to this device's slots."""
    S = merged_keys.shape[0]
    pos = jnp.clip(jnp.searchsorted(merged_keys, local_strata.keys), 0, S - 1)
    hit = local_strata.valid & (merged_keys[pos] == local_strata.keys)
    return jnp.where(hit, merged_vals[pos], fill)


# ---------------------------------------------------------------------------
# Per-device stage functions (run inside shard_map), mirroring
# core/join.py's prepare / exact / sample split.
# ---------------------------------------------------------------------------

class DistPrepareOut(NamedTuple):
    """Distributed stages 1-3 output.

    ``sorted_rels``/``local_strata`` are per-device (sharded) working state;
    ``strata``/``population``/counters are replicated and already merged into
    the canonical single-device layout, ready for host-side decisions.
    """

    sorted_rels: list[Relation]         # per-device shuffled + sorted rows
    local_strata: Strata                # per-device [S] slots
    strata: Strata                      # merged canonical [S] (replicated)
    live_counts: jnp.ndarray            # int32 [n] global
    total_counts: jnp.ndarray           # int32 [n] global
    population: jnp.ndarray             # f32 [S] merged
    shuffled_tuple_bytes: jnp.ndarray   # f32 [] global live bytes moved
    device_shuffled_bytes: jnp.ndarray  # f32 [k] per-device bytes sent
    bucket_overflow: jnp.ndarray        # int32 [] global dropped rows
    device_dropped: jnp.ndarray         # int32 [k] per-device dropped rows
    filter_bytes: jnp.ndarray           # f32 [] filter traffic (model)


def dist_prepare_stage(rels: Sequence[Relation], num_blocks: int,
                       max_strata: int, seed, axes: Sequence[str],
                       *, bucket_cap: Optional[int] = None,
                       filter_words: Optional[Sequence[jnp.ndarray]] = None,
                       filter_stage: bool = True,
                       merge: str = "gather") -> DistPrepareOut:
    """Filter build/OR/AND/probe, key shuffle, local sort + group-by, merge.

    ``filter_words`` (one ``[num_blocks, W]`` array per input) skips the
    build+OR — the serving engine passes its per-dataset cached dataset
    filters here so registered datasets pay the build once, not every step.

    ``merge='gather'`` rebuilds the canonical [S] strata (replicated) for
    the bit-parity path.  ``merge='psum'`` skips the gather entirely — the
    ``strata``/``population`` members are then the PER-DEVICE strata (with a
    psum'd overflow), keeping the paper's cheap-collective dataflow intact
    for the roofline dry-runs.
    """
    axes = tuple(axes)
    k = 1
    for a in axes:
        k *= jax.lax.axis_size(a)
    n_rels = len(rels)
    local_n = rels[0].capacity
    total_counts = jax.lax.psum(jnp.stack([r.count() for r in rels]), axes)

    if filter_stage:
        if filter_words is None:
            filter_words = [
                or_reduce(bloom.build(r.keys, r.valid, num_blocks, seed).words,
                          axes) for r in rels]
        jf = bloom.intersect_all(
            [bloom.BloomFilter(w, seed) for w in filter_words])
        rels = [Relation(r.keys, r.values,
                         r.valid & bloom.contains(jf, r.keys)) for r in rels]
        # all-gather restatement of the §3.1 (n + 1) filter-exchange model
        # (see core.join.filter_exchange_bytes): each of the n + 1 logical
        # filter transfers costs (k - 1) device hops on a k-device mesh
        fbytes = jnp.asarray(num_blocks * bloom.WORDS_PER_BLOCK * 4
                             * (k - 1) * (n_rels + 1), jnp.float32)
    else:
        fbytes = jnp.zeros((), jnp.float32)
    live_counts = jax.lax.psum(jnp.stack([r.count() for r in rels]), axes)

    # One partitioner for ALL relations (cogroup semantics) — matching keys
    # must land on the same device or strata never meet.  cap = local_n can
    # never overflow (a source holds local_n rows total); smaller caps trade
    # memory for counted drops.
    cap = bucket_cap or max(2 * local_n // k, 8)
    shuffled, sent_counts, overflows = [], [], []
    for r in rels:
        out, sent, ovf = shuffle_by_key(r, k, cap, axes, seed + 101)
        shuffled.append(out)
        sent_counts.append(sent)
        overflows.append(ovf)
    my_sent = (sum(sent_counts) * TUPLE_BYTES).astype(jnp.float32)
    device_sent = gather_concat(my_sent[None], axes)             # [k]
    sent_bytes = jnp.sum(device_sent)
    # dropped tuples are counted at the SENDING device (rows beyond the
    # bucket plan never leave it) — surfaced per device, never silent
    my_dropped = jnp.asarray(sum(overflows), jnp.int32)
    device_dropped = gather_concat(my_dropped[None], axes)       # [k]
    bucket_overflow = jnp.sum(device_dropped)

    sorted_rels = [sort_by_key(r) for r in shuffled]
    local_strata = build_strata(sorted_rels, max_strata)
    if merge == "psum":
        # no gather: every stratum keeps its per-device slot, overflow is
        # the summed per-device build overflow (what was actually dropped)
        local_strata = local_strata._replace(
            overflow=jax.lax.psum(local_strata.overflow, axes))
        return DistPrepareOut(sorted_rels, local_strata, local_strata,
                              live_counts, total_counts,
                              local_strata.population,
                              sent_bytes, device_sent, bucket_overflow,
                              device_dropped, fbytes)
    merged = merge_strata(local_strata, axes, max_strata)
    # replicate the (scalar) global overflow into the local strata too, so
    # both pytrees flowing out of a shard_map stage are well-defined
    local_strata = local_strata._replace(overflow=merged.overflow)
    return DistPrepareOut(sorted_rels, local_strata, merged,
                          live_counts, total_counts, merged.population,
                          sent_bytes, device_sent, bucket_overflow,
                          device_dropped, fbytes)


def dist_exact_stage(sorted_rels: Sequence[Relation], local_strata: Strata,
                     merged_strata: Strata, axes: Sequence[str], *,
                     agg: str = "sum", expr: str = "sum"):
    """§3.1.1 exact path: per-device per-stratum sums, merged, finished.

    ``per_stratum_value_sums`` adds each stratum's own rows only, in their
    sorted order, so each device reproduces the single-device per-stratum
    sums bit-for-bit; it reads row slots off ``local_strata``'s segments,
    which ``build_strata`` located in these same ``sorted_rels`` (the merged
    strata carry none).  The merge re-slots the sums and
    ``exact_stage_from_sums`` is the same finishing arithmetic the
    single-device stage runs.
    """
    S = merged_strata.keys.shape[0]
    S_k_local = per_stratum_value_sums(sorted_rels, local_strata)
    _, merged = merge_by_key(local_strata.keys,
                             [S_k_local[i] for i in range(S_k_local.shape[0])],
                             axes, S)
    S_k = jnp.stack([jnp.where(merged_strata.valid, m, 0.0) for m in merged])
    return exact_stage_from_sums(S_k, merged_strata, agg=agg, expr=expr)


def dist_sample_stage(sorted_rels: Sequence[Relation], local_strata: Strata,
                      merged_keys: jnp.ndarray, merged_valid: jnp.ndarray,
                      b_merged: jnp.ndarray, b_max: int, seed,
                      axes: Sequence[str], *,
                      agg: str = "sum", dedup: bool = False,
                      confidence: float = 0.95, f_fn=None):
    """Stages 4-6, distributed: local draws, merged stats, canonical finish.

    ``b_merged`` is the host-decided per-stratum sample size in the MERGED
    [S] layout (the same array a single-device driver would produce); it is
    routed back to each device's local slots by key.  Draws are keyed on the
    join key, so the owning device reproduces the single-device per-stratum
    sufficient statistics exactly; the merge re-slots them and the estimator
    runs on a bit-identical [S] stats array.
    """
    S = merged_keys.shape[0]
    b_local = merged_to_local(merged_keys, local_strata,
                              jnp.asarray(b_merged, jnp.float32))
    f = EXPRS["sum"][0] if f_fn is None else f_fn
    sample = sample_edges(sorted_rels, local_strata, b_local, b_max, seed, f)
    st = sample.stats
    _, merged = merge_by_key(
        local_strata.keys,
        [st.valid, st.population, st.n_sampled, st.sum_f, st.sum_f2,
         sample.unique_f, sample.unique_count], axes, S)
    ok = merged[0] & merged_valid
    z = jnp.zeros((), jnp.float32)
    vals = [jnp.where(ok, m, z) for m in merged[1:]]
    mstats = StratumStats(ok, *vals[:4])
    msample = SampleResult(mstats, vals[4], vals[5],
                           jnp.zeros((1, 1)), jnp.zeros((1, 1), bool))
    value, err, cnt, dof = estimate_stage(msample, agg=agg, dedup=dedup,
                                          confidence=confidence)
    return value, err, cnt, dof, mstats


def _psum_parts(parts: SumParts, axes) -> SumParts:
    return SumParts(*[jax.lax.psum(x, axes) for x in parts])


def dist_exact_stage_psum(sorted_rels: Sequence[Relation],
                          local_strata: Strata, axes: Sequence[str], *,
                          agg: str = "sum", expr: str = "sum"):
    """Exact path, paper dataflow: per-device totals merged by one psum.

    Strata are device-complete after the shuffle, so per-device exact
    aggregates ADD across devices — no strata gather, no canonical re-slot.
    Results agree with the gather merge up to float reassociation.
    """
    exact_fn = {"sum": exact_sum_of_sums,
                "product": exact_sum_of_products}[expr]
    est = jax.lax.psum(exact_fn(sorted_rels, local_strata), axes)
    cnt = jax.lax.psum(exact_count(local_strata), axes)
    if agg == "count":
        est = cnt
    elif agg == "avg":
        est = est / jnp.maximum(cnt, 1.0)
    return est, cnt


def dist_sample_stage_psum(sorted_rels: Sequence[Relation],
                           local_strata: Strata, b_local: jnp.ndarray,
                           b_max: int, seed, axes: Sequence[str], *,
                           agg: str = "sum", dedup: bool = False,
                           confidence: float = 0.95, f_fn=None):
    """Stages 4-6, paper dataflow (§3.3-III): local draws, psum'd parts.

    ``b_local`` is the per-stratum budget in THIS device's slot layout
    (the driver decides over the concatenation of per-device strata and
    each device receives its slice).  Every estimator is a sum of
    per-stratum terms and strata are device-complete, so the merge is a
    single psum of the sufficient parts — the cheapest collective the mesh
    offers, at the cost of bit-parity with the single-device pipeline
    (statistical equivalence is what the accuracy gate asserts).
    """
    f = EXPRS["sum"][0] if f_fn is None else f_fn
    sample = sample_edges(sorted_rels, local_strata,
                          jnp.asarray(b_local, jnp.float32), b_max, seed, f)
    st = sample.stats
    cnt = jax.lax.psum(clt_count(st), axes)
    if dedup:
        parts = HTParts(*[jax.lax.psum(x, axes) for x in
                          ht_sum_parts(st, sample.unique_f,
                                       sample.unique_count)])
        est = ht_finish(parts, confidence)
    else:
        parts = _psum_parts(clt_sum_parts(st), axes)
        if agg == "avg":
            est = clt_avg_from(parts, confidence)
        elif agg == "stdev":
            tau2 = jax.lax.psum(clt_sum_parts(second_moment_stats(st)).tau,
                                axes)
            est = clt_stdev_from(parts, tau2, confidence)
        else:
            est = clt_finish(parts, confidence)
    value = cnt if agg == "count" else est.estimate
    err = jnp.zeros_like(est.error_bound) if agg == "count" \
        else est.error_bound
    return value, err, cnt, est.dof, st


def make_distributed_join(mesh: Mesh,
                          *,
                          n_rels: int,
                          join_axes: Sequence[str] = ("data",),
                          mode: str = "sample",      # 'sample' | 'exact'
                          filter_stage: bool = True,  # False -> repartition
                          expr: str = "sum",
                          fp_rate: float = 0.01,
                          sample_fraction: Optional[float] = None,
                          budget: Optional[QueryBudget] = None,
                          cost_model: Optional[CostModel] = None,
                          bucket_cap: Optional[int] = None,
                          max_strata: Optional[int] = None,
                          b_max: int = 1024,
                          confidence: float = 0.95,
                          num_blocks: Optional[int] = None,
                          merge: str = "gather",     # 'gather' | 'psum'
                          seed: int = 0):
    """Build a jitted SPMD join over ``mesh``.

    The returned callable takes ``n_rels`` global Relations (leading dim
    sharded over ``join_axes``) plus a traced ``d_dt`` scalar (measured filter
    latency, feeds the latency cost function) and returns a
    :class:`DistJoinResult` of replicated scalars.

    ``merge='gather'`` (default) reproduces the single-device pipeline
    bit-for-bit; ``merge='psum'`` is the paper's partial-aggregate merge
    (cheapest collectives — what the cluster-scale roofline dry-runs lower).

    Static choices (mode, filtering, capacities) are compile-time — the
    "driver" decides them; re-compilation on change is the Spark-stage
    analogue and keeps every device step a fixed dense program.
    """
    axes = tuple(join_axes)
    k = 1
    for a in axes:
        k *= mesh.shape[a]
    f_fn, _ = EXPRS[expr]
    if budget is not None and budget.latency_s is not None:
        assert cost_model is not None
    assert merge in ("gather", "psum"), merge

    def body(d_dt, *flat):
        rels = [Relation(*flat[3 * i: 3 * i + 3]) for i in range(n_rels)]
        local_n = rels[0].capacity
        S = max_strata or k * (bucket_cap or max(2 * local_n // k, 8))
        prep = dist_prepare_stage(rels, num_blocks, S, seed, axes,
                                  bucket_cap=bucket_cap,
                                  filter_stage=filter_stage, merge=merge)
        live_total = jnp.sum(prep.live_counts).astype(jnp.float32)
        input_total = jnp.sum(prep.total_counts).astype(jnp.float32)
        # psum mode: population is per-device, so the global total is a psum
        total_pop = jnp.sum(prep.population)
        if merge == "psum":
            total_pop = jax.lax.psum(total_pop, axes)
        meters = dict(
            shuffled_tuple_bytes=prep.shuffled_tuple_bytes,
            filter_bytes=prep.filter_bytes,
            live_total=live_total,
            input_total=input_total,
            overlap_fraction=live_total / jnp.maximum(input_total, 1),
            bucket_overflow=prep.bucket_overflow,
            strata_overflow=prep.strata.overflow,
            total_population=total_pop,
            device_shuffled_bytes=prep.device_shuffled_bytes,
            device_dropped=prep.device_dropped,
        )

        if mode == "exact":
            if merge == "psum":
                est, cnt = dist_exact_stage_psum(prep.sorted_rels,
                                                 prep.local_strata, axes,
                                                 agg="sum", expr=expr)
            else:
                est, cnt = dist_exact_stage(prep.sorted_rels,
                                            prep.local_strata, prep.strata,
                                            axes, agg="sum", expr=expr)
            return DistJoinResult(est, jnp.zeros(()), cnt, jnp.zeros(()),
                                  sample_draws=jnp.zeros(()), **meters)

        # --- stage 4: b_i from the budget (§3.2) ---
        if sample_fraction is not None:
            s = jnp.asarray(sample_fraction, jnp.float32)
        elif budget is not None and budget.latency_s is not None:
            s = fraction_for_latency(cost_model, budget.latency_s, d_dt,
                                     total_pop)
        elif budget is not None and budget.error is not None:
            s = jnp.asarray(budget.pilot_fraction, jnp.float32)
        else:
            raise ValueError("sample mode needs a fraction or a budget")

        # --- stage 5: sample during join + merge (§3.3/§3.4) ---
        if merge == "psum":
            # size b_i straight off each device's own strata — every local
            # stratum gets its budget (no global-[S] truncation)
            b_local = _pilot_sizes(prep.local_strata.population, s)
            value, err, cnt, dof, st = dist_sample_stage_psum(
                prep.sorted_rels, prep.local_strata, b_local, b_max,
                seed + 1, axes, agg="sum", confidence=confidence, f_fn=f_fn)
            return DistJoinResult(value, err, cnt, dof,
                                  sample_draws=jax.lax.psum(
                                      jnp.sum(st.n_sampled), axes), **meters)
        b_merged = _pilot_sizes(prep.population, s)
        value, err, cnt, dof, mstats = dist_sample_stage(
            prep.sorted_rels, prep.local_strata, prep.strata.keys,
            prep.strata.valid, b_merged, b_max, seed + 1, axes,
            agg="sum", dedup=False, confidence=confidence, f_fn=f_fn)
        return DistJoinResult(value, err, cnt, dof,
                              sample_draws=jnp.sum(mstats.n_sampled),
                              **meters)

    rel_spec = [P(axes), P(axes), P(axes)] * n_rels
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), *rel_spec),
                       out_specs=DistJoinResult(
                           *([P()] * len(DistJoinResult._fields))),
                       check_vma=False)

    @jax.jit
    def run(rels: Sequence[Relation], d_dt=0.0):
        flat = [x for r in rels for x in (r.keys, r.values, r.valid)]
        return fn(jnp.asarray(d_dt, jnp.float32), *flat)

    return run


def distributed_approx_join(mesh: Mesh, rels: Sequence[Relation],
                            fp_rate: float = 0.01, **kw) -> DistJoinResult:
    """Convenience wrapper: size the filter from the inputs and run once."""
    num_blocks = bloom.num_blocks_for(max(r.capacity for r in rels), fp_rate)
    run = make_distributed_join(mesh, n_rels=len(rels), fp_rate=fp_rate,
                                num_blocks=num_blocks, **kw)
    return run(rels)


# ---------------------------------------------------------------------------
# Serving executables: batched (vmap over query slots) distributed stages,
# one shard_map program per stage so the JoinServer's executable cache keys
# (stage, shape_class, batch) work identically for both backends.  Each
# jitted function carries its stage's name, which the device trace gives
# its executable (``jit_serve_exact_mesh(<hash>)``).
# ---------------------------------------------------------------------------

def _rel_specs(axes, n):
    s = P(None, axes)
    return [Relation(s, s, s) for _ in range(n)]


def _local_strata_spec(axes):
    sharded = P(None, axes)
    return Strata(keys=sharded, valid=sharded,
                  starts=P(None, None, axes), counts=P(None, None, axes),
                  overflow=P())


def make_serve_prepare(mesh: Mesh, axes: Sequence[str], *, n_rels: int,
                       num_blocks: int, max_strata: int,
                       bucket_cap: Optional[int] = None,
                       merge: str = "gather"):
    """Batched distributed prepare: ``(rels_b, words_b, seeds) -> prep``.

    ``rels_b``: list of Relations with fields ``[B, N]``, sharded over
    ``axes`` on the row dim.  ``words_b``: ``[B, n, nb, W]`` replicated
    prebuilt dataset-filter words.  Returns a :class:`DistPrepareOut` whose
    per-device members stay sharded (feed them straight into the sample /
    exact executables) and whose merged members are replicated.

    ``merge='psum'`` skips the strata gather entirely: ``strata`` /
    ``population`` come back SHARDED — the host sees the concatenation of
    per-device strata (device d's slots at columns ``[d*S, (d+1)*S)``),
    which is a complete, disjoint cover of the global strata (every key
    lives on exactly one device after the shuffle), just not in the
    canonical key-sorted order.  Host-side sample sizing works unchanged on
    that layout; the psum sample/exact executables take each device's slice
    back via the same sharding.
    """
    axes = tuple(axes)
    assert merge in ("gather", "psum"), merge

    def per_query(flat, words, seed):
        rels = [Relation(*flat[3 * i: 3 * i + 3]) for i in range(n_rels)]
        return dist_prepare_stage(
            rels, num_blocks, max_strata, seed, axes, bucket_cap=bucket_cap,
            filter_words=[words[i] for i in range(n_rels)], merge=merge)

    def batched(*args):
        return jax.vmap(per_query)(*args)

    flat_spec = tuple(P(None, axes) for _ in range(3 * n_rels))
    strata_spec = _local_strata_spec(axes) if merge == "psum" \
        else Strata(P(), P(), P(), P(), P())
    out_spec = DistPrepareOut(
        sorted_rels=_rel_specs(axes, n_rels),
        local_strata=_local_strata_spec(axes),
        strata=strata_spec,
        live_counts=P(), total_counts=P(),
        population=P(None, axes) if merge == "psum" else P(),
        shuffled_tuple_bytes=P(), device_shuffled_bytes=P(),
        bucket_overflow=P(), device_dropped=P(), filter_bytes=P())
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=(flat_spec, P(), P()),
                       out_specs=out_spec, check_vma=False)

    @jax.jit
    def serve_prepare_mesh(rels_b: Sequence[Relation], words_b, seeds):
        flat = tuple(x for r in rels_b for x in (r.keys, r.values, r.valid))
        return fn(flat, words_b, seeds)

    return serve_prepare_mesh


def make_serve_sample(mesh: Mesh, axes: Sequence[str], *, n_rels: int,
                      b_max: int, agg: str, dedup: bool, confidence: float,
                      expr: str):
    """Batched distributed sample+estimate executable."""
    axes = tuple(axes)
    f_fn = EXPRS[expr][0]

    def per_query(flat, lstrata, mkeys, mvalid, b_merged, seed):
        sorted_rels = [Relation(*flat[3 * i: 3 * i + 3])
                       for i in range(n_rels)]
        return dist_sample_stage(sorted_rels, lstrata, mkeys, mvalid,
                                 b_merged, b_max, seed, axes, agg=agg,
                                 dedup=dedup, confidence=confidence, f_fn=f_fn)

    def batched(*args):
        return jax.vmap(per_query)(*args)

    flat_spec = tuple(P(None, axes) for _ in range(3 * n_rels))
    stats_spec = StratumStats(P(), P(), P(), P(), P())
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=(flat_spec, _local_strata_spec(axes), P(), P(),
                                 P(), P()),
                       out_specs=(P(), P(), P(), P(), stats_spec),
                       check_vma=False)

    @jax.jit
    def serve_sample_mesh(sorted_rels, lstrata, mkeys, mvalid, b_merged,
                          seeds):
        flat = tuple(x for r in sorted_rels
                     for x in (r.keys, r.values, r.valid))
        return fn(flat, lstrata, mkeys, mvalid, b_merged, seeds)

    return serve_sample_mesh


def make_serve_exact(mesh: Mesh, axes: Sequence[str], *, n_rels: int,
                     agg: str, expr: str):
    """Batched distributed exact-path executable."""
    axes = tuple(axes)

    def per_query(flat, lstrata, mstrata):
        sorted_rels = [Relation(*flat[3 * i: 3 * i + 3])
                       for i in range(n_rels)]
        return dist_exact_stage(sorted_rels, lstrata, mstrata, axes,
                                agg=agg, expr=expr)

    def batched(*args):
        return jax.vmap(per_query)(*args)

    flat_spec = tuple(P(None, axes) for _ in range(3 * n_rels))
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=(flat_spec, _local_strata_spec(axes),
                                 Strata(P(), P(), P(), P(), P())),
                       out_specs=(P(), P()), check_vma=False)

    @jax.jit
    def serve_exact_mesh(sorted_rels, lstrata, mstrata):
        flat = tuple(x for r in sorted_rels
                     for x in (r.keys, r.values, r.valid))
        return fn(flat, lstrata, mstrata)

    return serve_exact_mesh


def make_serve_sample_psum(mesh: Mesh, axes: Sequence[str], *, n_rels: int,
                           b_max: int, agg: str, dedup: bool,
                           confidence: float, expr: str):
    """Batched psum-merge sample+estimate executable.

    ``b`` arrives in the concatenated per-device layout ``[B, k*S]`` (the
    same layout ``make_serve_prepare(merge='psum')`` emitted its strata in);
    sharding it over ``axes`` hands every device exactly its own slice.
    Estimates come back replicated; the per-stratum stats stay sharded so
    the host reads the same concatenated layout it sized ``b`` in.
    """
    axes = tuple(axes)
    f_fn = EXPRS[expr][0]

    def per_query(flat, lstrata, b_local, seed):
        sorted_rels = [Relation(*flat[3 * i: 3 * i + 3])
                       for i in range(n_rels)]
        return dist_sample_stage_psum(sorted_rels, lstrata, b_local, b_max,
                                      seed, axes, agg=agg, dedup=dedup,
                                      confidence=confidence, f_fn=f_fn)

    def batched(*args):
        return jax.vmap(per_query)(*args)

    flat_spec = tuple(P(None, axes) for _ in range(3 * n_rels))
    sharded = P(None, axes)
    stats_spec = StratumStats(sharded, sharded, sharded, sharded, sharded)
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=(flat_spec, _local_strata_spec(axes), sharded,
                                 P()),
                       out_specs=(P(), P(), P(), P(), stats_spec),
                       check_vma=False)

    @jax.jit
    def serve_sample_psum(sorted_rels, lstrata, b, seeds):
        flat = tuple(x for r in sorted_rels
                     for x in (r.keys, r.values, r.valid))
        return fn(flat, lstrata, b, seeds)

    return serve_sample_psum


def make_serve_exact_psum(mesh: Mesh, axes: Sequence[str], *, n_rels: int,
                          agg: str, expr: str):
    """Batched psum-merge exact-path executable."""
    axes = tuple(axes)

    def per_query(flat, lstrata):
        sorted_rels = [Relation(*flat[3 * i: 3 * i + 3])
                       for i in range(n_rels)]
        return dist_exact_stage_psum(sorted_rels, lstrata, axes,
                                     agg=agg, expr=expr)

    def batched(*args):
        return jax.vmap(per_query)(*args)

    flat_spec = tuple(P(None, axes) for _ in range(3 * n_rels))
    fn = jax.shard_map(batched, mesh=mesh,
                       in_specs=(flat_spec, _local_strata_spec(axes)),
                       out_specs=(P(), P()), check_vma=False)

    @jax.jit
    def serve_exact_psum(sorted_rels, lstrata):
        flat = tuple(x for r in sorted_rels
                     for x in (r.keys, r.values, r.valid))
        return fn(flat, lstrata)

    return serve_exact_psum


def make_serve_filter_build(mesh: Mesh, axes: Sequence[str], *,
                            num_blocks: int):
    """Distributed dataset-filter build: sharded Relation -> replicated words.

    The OR-reduce of per-device partition filters equals the single-device
    build bit-for-bit (scatter-OR is a set union), so cached words from this
    executable are interchangeable with single-device ones.
    """
    axes = tuple(axes)

    def serve_filter_build_mesh(keys, valid, seed):
        return or_reduce(bloom.build(keys, valid, num_blocks, seed).words,
                         axes)

    fn = jax.shard_map(serve_filter_build_mesh, mesh=mesh,
                       in_specs=(P(axes), P(axes), P()), out_specs=P(),
                       check_vma=False)
    return jax.jit(fn)
