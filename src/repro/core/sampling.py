"""Stratified sampling *during* the join (paper §3.3, Algorithm 2).

The join of n relations on key C_i is the complete n-partite graph over the
per-side tuple groups; sampling the join output = sampling edges from that
graph without materializing it.  Per stratum (join key) we draw ``b_i`` edges
by picking one endpoint per side with a counter-based stateless hash:

    idx_side = start_side + counter_hash(seed, key, draw, side) % count_side

Everything is vectorized over a static [S, b_max] grid (S = strata capacity,
b_max = per-stratum draw capacity) — there is no per-key loop, matching the
"dense pass" TPU constraint (DESIGN.md §2).  Draws are keyed by the *join key*
(not the stratum index), so the sample is invariant to how tuples were
partitioned across devices — the coordination-free property the paper needs
for distributed sampling, made exact here.

The group-by machinery (``build_strata``) identifies strata from the sorted
lead relation, reads their segments in the lead off its own key runs, and
locates them in every other side by one merge of the sorted stratum keys
with the side's sorted keys (one sort, one prefix sum, one scatter) — no
binary search, no hash tables, no dynamic shapes.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp

from repro.core.estimators import StratumStats
from repro.core.hashing import GOLDEN, bounded, counter_hash, fmix32, hash2, u32
from repro.core.relation import Relation

SENTINEL = 0xFFFFFFFF  # invalid-row key fill; real keys must be < 2^32 - 1


class Strata(NamedTuple):
    """Join strata: one row per distinct key of the (sorted) lead relation.

    ``starts``/``counts`` are [n_sides, S]: the segment of each stratum in
    each side's sorted key array.  ``joinable`` marks strata present (count>0)
    on every side — only those produce join output.
    """

    keys: jnp.ndarray      # uint32 [S]
    valid: jnp.ndarray     # bool   [S] stratum slot holds a real key
    starts: jnp.ndarray    # int32  [n_sides, S]
    counts: jnp.ndarray    # int32  [n_sides, S]
    overflow: jnp.ndarray  # int32  [] strata beyond capacity S (diagnostic)

    @property
    def joinable(self) -> jnp.ndarray:
        return self.valid & jnp.all(self.counts > 0, axis=0)

    @property
    def population(self) -> jnp.ndarray:
        """B_i — join-output size per stratum (product of side counts)."""
        p = jnp.prod(jnp.maximum(self.counts, 0).astype(jnp.float32), axis=0)
        return jnp.where(self.joinable, p, 0.0)

    @property
    def num_strata(self) -> jnp.ndarray:
        """m — number of joinable strata."""
        return jnp.sum(self.joinable.astype(jnp.int32))


def _merge_segments(side_keys: jnp.ndarray, stratum_keys: jnp.ndarray):
    """(start, count) of each stratum key's run in the ascending
    ``side_keys``: what ``searchsorted`` left and right give, from one merge.

    One stable sort of [stratum keys, their successors key + 1, side keys]
    puts each of the first two before the side keys equal to it, so the
    side keys counted up to it are the side keys below it: for a stratum
    key its start, for its successor its end (the side keys at or below
    the stratum key).  Both are scattered back to the strata's slots and
    the side's elements are dropped.  A stratum keyed SENTINEL, whose
    successor wraps to 0, ends at the end of the side.
    """
    S, n = stratum_keys.shape[0], side_keys.shape[0]
    _, at = jax.lax.sort(
        (jnp.concatenate([stratum_keys, stratum_keys + u32(1), side_keys]),
         jnp.arange(2 * S + n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    below = jnp.cumsum((at >= 2 * S).astype(jnp.int32))
    bounds = jnp.zeros((2 * S,), jnp.int32).at[jnp.where(
        at < 2 * S, at, 2 * S)].set(below, mode="drop")
    ends = jnp.where(stratum_keys == u32(SENTINEL), n, bounds[S:])
    return bounds[:S], ends - bounds[:S]


@jax.named_scope("strata")
def build_strata(sorted_rels: Sequence[Relation], max_strata: int) -> Strata:
    """Identify strata from sorted_rels[0]; locate segments in every side.

    Preconditions: every relation is sorted by ``masked_keys(SENTINEL)``
    (invalid rows filled with SENTINEL sort last, as ``sort_by_key`` does);
    the stratum keys built here are then ascending and distinct, but for
    the SENTINEL pads of unused slots (a valid lead row keyed SENTINEL makes
    one valid stratum keyed SENTINEL, last).

    Each stratum is a run of the lead's masked keys that starts at a valid
    row, and that run is its segment in the lead: from its first row to the
    next stratum's first row, read off the same scatter as its key.  In
    every other side its segment is found by one merge of the stratum keys
    with the side's masked keys (:func:`_merge_segments`).  Either way
    ``starts``/``counts`` are what ``searchsorted(side, key, "left")`` and
    the right search's difference give, also in unused slots (start: the
    side's rows below SENTINEL; count 0).  Strata beyond ``max_strata`` are
    counted in ``overflow`` (they are dropped; callers size S = key capacity
    to make this impossible in exact mode).
    """
    lead = sorted_rels[0]
    mk = lead.masked_keys(SENTINEL)
    n = mk.shape[0]
    first = jnp.ones((min(n, 1),), bool)
    is_start = lead.valid & jnp.concatenate([first, mk[1:] != mk[:-1]])
    sid = jnp.cumsum(is_start.astype(jnp.int32)) - 1  # stratum index per row
    total = jnp.sum(is_start.astype(jnp.int32))
    S = max_strata
    # row S takes stratum S (where the lead's slot S - 1 ends); later drop
    slot = jnp.where(is_start, sid, S + 1)
    keys = jnp.full((S + 1,), SENTINEL, jnp.uint32).at[slot].set(mk,
                                                                 mode="drop")
    keys = keys[:S]
    valid = jnp.arange(S) < jnp.minimum(total, S)
    keys = jnp.where(valid, keys, u32(SENTINEL))
    # the lead: stratum i's run starts at its is_start row and ends where
    # stratum i + 1's starts, or else where the masked keys reach SENTINEL
    # (which is also where the left search of SENTINEL lands); a stratum
    # keyed SENTINEL runs to the end
    below = jnp.sum((mk != u32(SENTINEL)).astype(jnp.int32))
    bounds = jnp.full((S + 1,), below, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    ends = jnp.where(keys == u32(SENTINEL), n, bounds[1:])
    starts, counts = [bounds[:S]], [ends - bounds[:S]]
    for r in sorted_rels[1:]:
        s, c = _merge_segments(r.masked_keys(SENTINEL), keys)
        starts.append(s)
        counts.append(c)
    counts = [jnp.where(valid, c, 0) for c in counts]
    return Strata(keys, valid,
                  jnp.stack(starts), jnp.stack(counts),
                  jnp.maximum(total - S, 0))


def edge_indices(strata: Strata, b_max: int, seed) -> jnp.ndarray:
    """Draw endpoint indices for every (stratum, draw, side).

    Returns int32 [n_sides, S, b_max] — absolute row indices into each side's
    sorted arrays.  Pure function of (seed, join key, draw counter, side):
    deterministic, replayable, partition-invariant.
    """
    n_sides, S = strata.starts.shape
    t = jnp.arange(b_max, dtype=jnp.uint32)[None, :]          # [1, b_max]
    keys = strata.keys[:, None]                               # [S, 1]
    idx = []
    for side in range(n_sides):
        h = counter_hash(seed, keys, t, side)                 # [S, b_max]
        cnt = jnp.maximum(strata.counts[side], 1)[:, None]
        idx.append(strata.starts[side][:, None] + bounded(h, cnt))
    return jnp.stack(idx)


def edge_id(idx_in_stratum: jnp.ndarray) -> jnp.ndarray:
    """Collision-resistant id of an edge from per-side in-stratum offsets.

    [n_sides, S, b_max] -> uint32 [S, b_max].  Hash-combined (a true mixed
    radix id can overflow u32 for large strata); collision probability within
    a stratum is ~b_max^2 / 2^33 — negligible at our draw capacities and only
    used for the HT dedup path (documented in DESIGN.md §8).
    """
    h = u32(0)
    for side in range(idx_in_stratum.shape[0]):
        h = fmix32(h * u32(GOLDEN) ^ u32(idx_in_stratum[side]))
    return h


class SampleResult(NamedTuple):
    stats: StratumStats       # with-replacement sufficient statistics
    unique_f: jnp.ndarray     # [S] sum of f over *distinct* edges (HT path)
    unique_count: jnp.ndarray # [S] number of distinct edges
    f_values: jnp.ndarray     # [S, b_max] sampled f(edge) (0 where masked)
    mask: jnp.ndarray         # bool [S, b_max] draw validity


def default_f(values: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """The paper's running aggregate: SUM(R1.V + R2.V + ... + Rn.V)."""
    out = values[0]
    for v in values[1:]:
        out = out + v
    return out


@jax.named_scope("sampler")
def sample_edges(sorted_rels: Sequence[Relation], strata: Strata,
                 b_i: jnp.ndarray, b_max: int, seed,
                 f: Callable[[Sequence[jnp.ndarray]], jnp.ndarray]
                 = default_f) -> SampleResult:
    """Algorithm 2, vectorized: draw, gather, aggregate per stratum.

    ``b_i`` is float/int [S] — the per-stratum budget from the cost function
    (§3.2); actual draws are ``min(b_i, b_max)`` over joinable strata.
    """
    S = strata.keys.shape[0]
    idx = edge_indices(strata, b_max, seed)                   # [n, S, b_max]
    vals = [r.values[idx[side]] for side, r in enumerate(sorted_rels)]
    fv = f(vals)                                              # [S, b_max]
    t = jnp.arange(b_max, dtype=jnp.float32)[None, :]
    mask = (t < jnp.asarray(b_i, jnp.float32)[:, None]) & \
        strata.joinable[:, None]
    fm = jnp.where(mask, fv, 0.0)
    n_sampled = jnp.sum(mask, axis=1, dtype=jnp.float32)
    stats = StratumStats(
        valid=strata.joinable,
        population=strata.population,
        n_sampled=n_sampled,
        sum_f=jnp.sum(fm, axis=1),
        sum_f2=jnp.sum(fm * fm, axis=1),
    )
    # --- dedup path (Horvitz-Thompson, §3.4-II) ---
    eid = edge_id(idx - strata.starts[:, :, None])            # [S, b_max]
    eid = jnp.where(mask, eid, u32(SENTINEL))
    order = jnp.argsort(eid, axis=1)
    eid_s = jnp.take_along_axis(eid, order, axis=1)
    fv_s = jnp.take_along_axis(fm, order, axis=1)
    first = jnp.concatenate(
        [jnp.ones((S, 1), bool), eid_s[:, 1:] != eid_s[:, :-1]], axis=1)
    keep = first & (eid_s != u32(SENTINEL))
    unique_f = jnp.sum(jnp.where(keep, fv_s, 0.0), axis=1)
    unique_count = jnp.sum(keep, axis=1, dtype=jnp.float32)
    return SampleResult(stats, unique_f, unique_count, fm, mask)


# ---------------------------------------------------------------------------
# Exact aggregates from sufficient statistics (DESIGN.md §2, beyond-paper).
# The cartesian structure of the join makes SUM-type aggregates separable:
#   sum over edges of  sum_k v_k  =  sum_k ( S_k * prod_{j != k} B_j )
#   sum over edges of prod_k v_k  =  prod_k S_k
# computed per stratum in one segment-sum pass — O(N), no cross product.
# Used as the oracle in tests and as the exact fast path when no budget is
# given and the overlap is large.
# ---------------------------------------------------------------------------

@jax.named_scope("exact_sum")
def per_stratum_value_sums(sorted_rels, strata) -> jnp.ndarray:
    """[n_sides, S] sum of values per stratum per side.

    Requires ``strata`` to have been built by ``build_strata`` from these
    same ``sorted_rels``: each row's stratum slot is read off the segments
    ``strata.starts``/``strata.counts`` (disjoint, in key order), with no
    per-row search.  Merged strata (``merge_strata``) carry no segments and
    must never reach this function.

    Scatter-add keyed by stratum slot rather than a cumsum-difference of the
    values: each stratum's sum then depends only on its OWN rows (same
    relative order), never on the rows sorted before it — which is what
    lets a device holding a shuffled subset of the strata reproduce the
    single-device per-stratum sums bit-for-bit (core/distributed.py relies
    on this).
    """
    S = strata.keys.shape[0]
    label = jnp.arange(1, S + 1, dtype=jnp.int32)
    sums = []
    for side, r in enumerate(sorted_rels):
        n = r.keys.shape[0]
        start, count = strata.starts[side], strata.counts[side]
        # +(i+1) where stratum i's segment opens, -(i+1) where it closes:
        # the prefix sum is i+1 inside it and 0 between segments.  Empty
        # and unused slots mark past the end (dropped): their cancelling
        # marks would otherwise pile onto one row and slow the scatter.
        opens = jnp.where(count > 0, start, n)
        closes = jnp.where(count > 0, start + count, n)
        edges = jnp.zeros((n,), jnp.int32).at[
            jnp.concatenate([opens, closes])].add(
            jnp.concatenate([label, -label]), mode="drop")
        inside = jnp.cumsum(edges)
        ok = r.valid & (inside > 0)
        tgt = jnp.where(ok, inside - 1, S)  # overflow row, dropped
        sums.append(jnp.zeros((S + 1,), jnp.float32).at[tgt].add(
            jnp.where(ok, r.values, 0.0))[:S])
    return jnp.stack(sums)


# Back-compat alias (pre-PR-2 private name).
_per_stratum_value_sums = per_stratum_value_sums


@jax.named_scope("exact_sum")
def exact_sum_of_sums_from(S_k: jnp.ndarray, strata: Strata) -> jnp.ndarray:
    """Finish SUM(v_1 + ... + v_n) from per-stratum value sums [n, S].

    Split out so the distributed pipeline can merge per-device S_k into the
    canonical [S] layout and then run the *same* finishing arithmetic as the
    single-device path (bit-identical results).
    """
    B_k = jnp.maximum(strata.counts, 0).astype(jnp.float32)   # [n, S]
    total_B = strata.population                               # [S]
    per_stratum = jnp.zeros_like(total_B)
    n = S_k.shape[0]
    for k in range(n):
        # NB: the select sits BETWEEN the multiply and the accumulate add, so
        # XLA cannot contract add(mul(..)) into an fma — fma rounds once, and
        # whether the contraction fires depends on what else is in the fused
        # computation, which would make the result depend on jit context
        # (eager vs jit(vmap(stage)) vs shard_map).  Bit-parity between the
        # driver, the serving engine, and the distributed pipeline needs this
        # arithmetic to be context-independent.
        term = jnp.where(B_k[k] > 0,
                         S_k[k] * (total_B / jnp.maximum(B_k[k], 1.0)), 0.0)
        per_stratum = per_stratum + term
    return jnp.sum(jnp.where(strata.joinable, per_stratum, 0.0))


@jax.named_scope("exact_sum")
def exact_sum_of_products_from(S_k: jnp.ndarray,
                               strata: Strata) -> jnp.ndarray:
    """Finish SUM(v_1 * ... * v_n) from per-stratum value sums [n, S]."""
    per_stratum = jnp.prod(S_k, axis=0)
    return jnp.sum(jnp.where(strata.joinable, per_stratum, 0.0))


def exact_sum_of_sums(sorted_rels, strata) -> jnp.ndarray:
    """Exact SUM(v_1 + ... + v_n) over the join output."""
    return exact_sum_of_sums_from(per_stratum_value_sums(sorted_rels, strata),
                                  strata)


def exact_sum_of_products(sorted_rels, strata) -> jnp.ndarray:
    """Exact SUM(v_1 * ... * v_n) over the join output."""
    return exact_sum_of_products_from(
        per_stratum_value_sums(sorted_rels, strata), strata)


def exact_count(strata: Strata) -> jnp.ndarray:
    return jnp.sum(strata.population)


# ---------------------------------------------------------------------------
# Merge-able per-stratum reservoirs (streaming, StreamApprox-style).
#
# A bounded uniform sample per stratum over an UNBOUNDED stream of values:
# every item gets a priority from the stateless counter hash keyed on its
# arrival identity (tick, row) — never on which reservoir folded it — and a
# stratum from its key hash; the reservoir is the bottom-``cap`` priorities
# per stratum.  Bottom-k by a uniform priority is a uniform without-
# replacement sample (the classic distributed-reservoir trick), and it makes
# the sketch *exactly* mergeable: bottom-k of a union only needs the
# bottom-k of each part, so ``extend(extend(E, A), B)`` equals
# ``merge(extend(E, A), extend(E, B))`` bit-for-bit (up to u32 priority
# ties, ~n^2/2^33).  Static [S, cap] shapes, one sort per fold — jittable,
# vmappable, and shardable like every other stage here.
# ---------------------------------------------------------------------------

class Reservoir(NamedTuple):
    """Per-stratum bottom-k value reservoir (priority SENTINEL = empty slot).

    ``n_seen`` counts every valid item ever offered per stratum — the
    denominator that turns the reservoir into rate/moment estimates.
    """

    priority: jnp.ndarray  # uint32 [S, cap], ascending per row
    values: jnp.ndarray    # f32    [S, cap]
    n_seen: jnp.ndarray    # f32    [S]


def reservoir_empty(num_strata: int, cap: int) -> Reservoir:
    return Reservoir(jnp.full((num_strata, cap), SENTINEL, jnp.uint32),
                     jnp.zeros((num_strata, cap), jnp.float32),
                     jnp.zeros((num_strata,), jnp.float32))


def _keep_bottom(priority: jnp.ndarray, values: jnp.ndarray, cap: int):
    order = jnp.argsort(priority, axis=1)
    return (jnp.take_along_axis(priority, order, axis=1)[:, :cap],
            jnp.take_along_axis(values, order, axis=1)[:, :cap])


def reservoir_extend(res: Reservoir, keys: jnp.ndarray, values: jnp.ndarray,
                     valid: jnp.ndarray, seed, tick) -> Reservoir:
    """Fold one micro-batch into the reservoir.

    ``tick`` is the arrival index of the batch (must be unique per fold of
    the same stream — priorities are ``counter_hash(seed, tick, row, 3)``, so
    reusing a tick would replay the same priorities).  Stratum assignment is
    ``hash2(key, seed) % S``.  Invalid rows are ignored everywhere.
    """
    S, cap = res.priority.shape
    n = keys.shape[0]
    sid = bounded(hash2(keys, seed), jnp.int32(S))               # [n]
    rows = jnp.arange(n, dtype=jnp.uint32)
    pri = counter_hash(seed, u32(tick), rows, 3)
    pri = jnp.where(pri == u32(SENTINEL), u32(SENTINEL - 1), pri)
    # stage only the incoming batch's bottom-cap per stratum (bottom-k of a
    # union needs only the bottom-k of each part): lexsort by (stratum,
    # priority), rank within the stratum run, keep ranks < cap — the final
    # per-row sort then runs over [S, 2*cap], independent of batch size
    d = jnp.where(valid, sid, S)
    order = jnp.lexsort((pri, d))
    ds = d[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), ds[1:] != ds[:-1]])
    slot = pos - jax.lax.cummax(jnp.where(is_start, pos, 0))
    ok = (ds < S) & (slot < cap)
    flat = jnp.where(ok, ds * cap + slot, S * cap)
    grid_p = jnp.full((S * cap + 1,), SENTINEL, jnp.uint32).at[flat].set(
        pri[order], mode="drop")[:-1].reshape(S, cap)
    grid_v = jnp.zeros((S * cap + 1,), jnp.float32).at[flat].set(
        values[order], mode="drop")[:-1].reshape(S, cap)
    p, v = _keep_bottom(jnp.concatenate([res.priority, grid_p], axis=1),
                        jnp.concatenate([res.values, grid_v], axis=1), cap)
    seen = jnp.zeros((S + 1,), jnp.float32).at[d].add(
        valid.astype(jnp.float32))[:S]
    return Reservoir(p, v, res.n_seen + seen)


def reservoir_merge(a: Reservoir, b: Reservoir) -> Reservoir:
    """Union of two reservoirs over disjoint (tick-distinct) sub-streams."""
    assert a.priority.shape == b.priority.shape, (a.priority.shape,
                                                 b.priority.shape)
    cap = a.priority.shape[1]
    p, v = _keep_bottom(jnp.concatenate([a.priority, b.priority], axis=1),
                        jnp.concatenate([a.values, b.values], axis=1), cap)
    return Reservoir(p, v, a.n_seen + b.n_seen)


def reservoir_fill(res: Reservoir) -> jnp.ndarray:
    """Occupied slots per stratum ([S] f32) — min(n_seen, cap)."""
    return jnp.sum((res.priority != u32(SENTINEL)).astype(jnp.float32),
                   axis=1)


def reservoir_moments(res: Reservoir):
    """(n [S], mean [S], var [S]) of the reservoir sample per stratum.

    Unbiased sample mean/variance of the stream per stratum (the reservoir
    is a uniform sample); feeds streaming sigma diagnostics.
    """
    m = res.priority != u32(SENTINEL)
    n = jnp.sum(m.astype(jnp.float32), axis=1)
    nz = jnp.maximum(n, 1.0)
    vm = jnp.where(m, res.values, 0.0)
    mean = jnp.sum(vm, axis=1) / nz
    var = jnp.sum(jnp.where(m, (res.values - mean[:, None]) ** 2, 0.0),
                  axis=1) / jnp.maximum(n - 1.0, 1.0)
    return n, mean, var
