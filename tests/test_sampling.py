"""Stratified-sampling machinery: group-by strata, segment location, edge
draws, exact sufficient-statistics oracles (hypothesis property tests)."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stubs
from repro.core.hashing import hash2
from repro.core.relation import relation, sort_by_key
from repro.core.sampling import (SENTINEL, build_strata, exact_count,
                                 exact_sum_of_products, exact_sum_of_sums,
                                 per_stratum_value_sums, reservoir_empty,
                                 reservoir_extend, reservoir_fill,
                                 reservoir_merge, reservoir_moments,
                                 sample_edges)

given, settings, st = hypothesis_or_stubs()

KEYS = st.lists(st.integers(0, 30), min_size=1, max_size=120)


def _sorted_rel(keys, rng):
    vals = rng.normal(2.0, 1.0, len(keys)).astype(np.float32)
    return sort_by_key(relation(np.array(keys, np.uint32), vals))


@settings(max_examples=30, deadline=None)
@given(KEYS, KEYS)
def test_strata_counts_match_numpy(k1, k2):
    rng = np.random.default_rng(0)
    r1, r2 = _sorted_rel(k1, rng), _sorted_rel(k2, rng)
    strata = build_strata([r1, r2], max_strata=64)
    got = {}
    keys = np.asarray(strata.keys)
    for i in range(64):
        if bool(strata.valid[i]):
            got[int(keys[i])] = (int(strata.counts[0, i]),
                                 int(strata.counts[1, i]))
    import collections
    c1 = collections.Counter(k1)
    c2 = collections.Counter(k2)
    want = {}
    # strata come from the lead relation after fmix-free sort: raw keys
    for k in c1:
        want[k] = (c1[k], c2.get(k, 0))
    assert got == want


@settings(max_examples=30, deadline=None)
@given(KEYS, KEYS)
def test_exact_sufficient_stats_vs_bruteforce(k1, k2):
    rng = np.random.default_rng(1)
    r1, r2 = _sorted_rel(k1, rng), _sorted_rel(k2, rng)
    strata = build_strata([r1, r2], max_strata=64)
    v1 = {"k": np.asarray(r1.keys), "v": np.asarray(r1.values)}
    v2 = {"k": np.asarray(r2.keys), "v": np.asarray(r2.values)}
    want_sum = want_prod = 0.0
    want_cnt = 0
    for i in range(len(v1["k"])):
        for j in range(len(v2["k"])):
            if v1["k"][i] == v2["k"][j]:
                want_cnt += 1
                want_sum += float(v1["v"][i]) + float(v2["v"][j])
                want_prod += float(v1["v"][i]) * float(v2["v"][j])
    np.testing.assert_allclose(float(exact_count(strata)), want_cnt,
                               rtol=1e-6)
    np.testing.assert_allclose(float(exact_sum_of_sums([r1, r2], strata)),
                               want_sum, rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(
        float(exact_sum_of_products([r1, r2], strata)), want_prod,
        rtol=2e-4, atol=1e-3)


def test_draws_respect_segments_and_budget():
    rng = np.random.default_rng(2)
    r1 = _sorted_rel(list(rng.integers(0, 20, 500)), rng)
    r2 = _sorted_rel(list(rng.integers(10, 30, 500)), rng)
    strata = build_strata([r1, r2], max_strata=64)
    b_i = jnp.minimum(strata.population, 7.0)
    res = sample_edges([r1, r2], strata, b_i, b_max=16, seed=3)
    n = np.asarray(res.stats.n_sampled)
    joinable = np.asarray(strata.joinable)
    want = np.where(joinable, np.minimum(np.asarray(b_i), 16), 0)
    np.testing.assert_array_equal(n, want)
    # all sampled f-values come from real value combinations: bounded
    vmax = float(np.abs(np.asarray(r1.values)).max()
                 + np.abs(np.asarray(r2.values)).max())
    assert float(np.abs(np.asarray(res.f_values)).max()) <= vmax + 1e-5


def test_sampler_is_partition_invariant():
    """Draws are keyed by (seed, join key, counter), not row position.

    With values that are a function of the key (so within-segment order
    cannot matter), permuting the input rows leaves EVERY per-stratum
    statistic bit-identical — the property that makes the distributed
    sampler coordination-free (DESIGN.md §2)."""
    rng = np.random.default_rng(4)
    k1 = np.array(list(rng.integers(0, 12, 300)), np.uint32)
    k2 = list(rng.integers(6, 18, 300))
    v1 = (k1 * 0.5 + 1.0).astype(np.float32)    # value determined by key
    r1a = sort_by_key(relation(k1, v1))
    r2a = _sorted_rel(k2, np.random.default_rng(6))
    perm = rng.permutation(300)
    r1b = sort_by_key(relation(k1[perm], v1[perm]))
    strata_a = build_strata([r1a, r2a], 32)
    res_a = sample_edges([r1a, r2a], strata_a, jnp.minimum(
        strata_a.population, 5.0), 8, seed=9)
    strata_b = build_strata([r1b, r2a], 32)
    res_b = sample_edges([r1b, r2a], strata_b, jnp.minimum(
        strata_b.population, 5.0), 8, seed=9)
    ka = np.asarray(strata_a.keys)
    kb = np.asarray(strata_b.keys)
    for field in ("n_sampled", "sum_f", "sum_f2"):
        sa = {int(k): float(s) for k, s, v in zip(
            ka, np.asarray(getattr(res_a.stats, field)),
            np.asarray(res_a.stats.valid)) if v}
        sb = {int(k): float(s) for k, s, v in zip(
            kb, np.asarray(getattr(res_b.stats, field)),
            np.asarray(res_b.stats.valid)) if v}
        assert sa == sb, field


def test_strata_overflow_counted():
    rng = np.random.default_rng(5)
    r1 = _sorted_rel(list(range(100)), rng)     # 100 distinct keys
    r2 = _sorted_rel(list(range(100)), rng)
    strata = build_strata([r1, r2], max_strata=32)
    assert int(strata.overflow) == 100 - 32
    assert int(strata.num_strata) == 32


def _searchsorted_value_sums(sorted_rels, strata):
    """Reference: each row's stratum slot by a binary search of the strata
    keys, checked against the key and the slot's validity, then the same
    scatter-add ``per_stratum_value_sums`` does."""
    S = strata.keys.shape[0]
    sums = []
    for r in sorted_rels:
        mk = r.masked_keys(SENTINEL)
        slot = jnp.clip(jnp.searchsorted(strata.keys, mk), 0, S - 1)
        ok = r.valid & (strata.keys[slot] == mk) & strata.valid[slot]
        tgt = jnp.where(ok, slot, S)
        sums.append(jnp.zeros((S + 1,), jnp.float32).at[tgt].add(
            jnp.where(ok, r.values, 0.0))[:S])
    return jnp.stack(sums)


# per side: (lowest key, highest key + 1, rows, share of rows valid), each
# side padded with invalid rows to 256; then max_strata
SEGMENT_CASES = {
    "two_way": ([(0, 40, 200, 0.8), (10, 50, 120, 0.8)], 64),
    "three_way": ([(0, 30, 200, 0.9), (5, 35, 150, 0.7),
                   (0, 20, 100, 1.0)], 64),
    "keys_in_one_side_only": ([(0, 20, 100, 1.0), (100, 140, 100, 1.0)],
                              64),
    "side_missing_strata": ([(0, 60, 200, 1.0), (0, 10, 50, 1.0)], 64),
    "strata_overflow": ([(0, 200, 250, 1.0), (0, 200, 250, 0.9)], 16),
    "all_invalid_side": ([(0, 40, 200, 1.0), (0, 40, 100, 0.0)], 64),
    "all_invalid_lead": ([(0, 40, 200, 0.0), (0, 40, 100, 1.0)], 64),
    # valid rows keyed SENTINEL share their segment with the invalid rows
    "sentinel_keys": ([(SENTINEL - 2, SENTINEL + 1, 200, 0.7),
                       (SENTINEL - 2, SENTINEL + 1, 100, 0.7)], 64),
}


def _segment_rels(cases, case):
    sides, max_strata = cases[case]
    rng = np.random.default_rng(sorted(cases).index(case))
    rels = []
    for lo, hi, n, share in sides:
        keys = rng.integers(lo, hi, 256).astype(np.uint32)
        vals = rng.normal(2.0, 1.0, 256).astype(np.float32)
        valid = (np.arange(256) < n) & (rng.random(256) < share)
        rels.append(sort_by_key(relation(keys, vals, valid)))
    return rels, max_strata


@pytest.mark.parametrize("case", list(SEGMENT_CASES))
def test_value_sums_match_searchsorted_slots(case):
    """Stratum slots read off the strata's segments give bit-identical
    per-stratum sums to a per-row search of the strata keys."""
    rels, max_strata = _segment_rels(SEGMENT_CASES, case)
    strata = build_strata(rels, max_strata)
    got = per_stratum_value_sums(rels, strata)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(
        _searchsorted_value_sums(rels, strata)))


def _searchsorted_strata(sorted_rels, max_strata):
    """Reference: strata keys from the lead's run starts, then each
    stratum's segment in every side by a left and a right binary search."""
    lead = sorted_rels[0]
    mk = lead.masked_keys(SENTINEL)
    is_start = lead.valid & jnp.concatenate(
        [jnp.ones((1,), bool), mk[1:] != mk[:-1]])
    sid = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    total = jnp.sum(is_start.astype(jnp.int32))
    S = max_strata
    slot = jnp.where(is_start & (sid < S), sid, S)
    keys = jnp.full((S + 1,), SENTINEL, jnp.uint32).at[slot].set(
        mk, mode="drop")[:S]
    valid = jnp.arange(S) < jnp.minimum(total, S)
    keys = jnp.where(valid, keys, jnp.uint32(SENTINEL))
    starts, counts = [], []
    for r in sorted_rels:
        side = r.masked_keys(SENTINEL)
        lo = jnp.searchsorted(side, keys, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(side, keys, side="right").astype(jnp.int32)
        starts.append(lo)
        counts.append(jnp.where(valid, hi - lo, 0))
    return (keys, valid, jnp.stack(starts), jnp.stack(counts),
            jnp.maximum(total - S, 0))


STRATA_CASES = {**SEGMENT_CASES,
                "sixteen_strata_long_side": ([(0, 16, 256, 1.0),
                                              (0, 24, 256, 1.0)], 16)}


@pytest.mark.parametrize("case", list(STRATA_CASES))
def test_strata_match_searchsorted_segments(case):
    """The lead's run starts and the other sides' merge give bit-identical
    strata to a binary search of every stratum key in every side."""
    rels, max_strata = _segment_rels(STRATA_CASES, case)
    got = build_strata(rels, max_strata)
    want = _searchsorted_strata(rels, max_strata)
    for field, w in zip(("keys", "valid", "starts", "counts", "overflow"),
                        want):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(w), err_msg=field)


# ---------------------------------------------------------------------------
# Merge-able per-stratum reservoirs (the streaming sketch).
# ---------------------------------------------------------------------------

def _batch(seed, n=256, hi=1000):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, hi, n).astype(np.uint32),
            rng.normal(3.0, 2.0, n).astype(np.float32),
            rng.random(n) < 0.9)


def test_reservoir_under_capacity_keeps_exact_multiset():
    keys, vals, valid = _batch(0, n=100)
    res = reservoir_extend(reservoir_empty(8, 100), jnp.asarray(keys),
                           jnp.asarray(vals), jnp.asarray(valid), 5, 0)
    got = np.sort(np.asarray(res.values)[
        np.asarray(res.priority) != np.uint32(0xFFFFFFFF)])
    np.testing.assert_array_equal(got, np.sort(vals[valid]))
    # n_seen counts offered valid rows per hash stratum
    sid = np.asarray(hash2(jnp.asarray(keys), 5)) % 8
    want = np.bincount(sid[valid], minlength=8)
    np.testing.assert_array_equal(np.asarray(res.n_seen), want)
    np.testing.assert_array_equal(np.asarray(reservoir_fill(res)), want)


def test_reservoir_bounded_overflow():
    keys, vals, valid = _batch(1, n=2048)
    res = reservoir_empty(4, 16)
    for tick in range(3):
        res = reservoir_extend(res, jnp.asarray(keys), jnp.asarray(vals),
                               jnp.asarray(valid), 5, tick)
    fill = np.asarray(reservoir_fill(res))
    np.testing.assert_array_equal(fill, np.full(4, 16))       # saturated
    assert float(np.asarray(res.n_seen).sum()) == 3 * valid.sum()
    # kept values are a subset of the offered ones
    assert set(np.asarray(res.values).ravel().tolist()) <= \
        set(vals[valid].tolist())


def test_reservoir_merge_equals_sequential_extend():
    """Bottom-k by item-identity priorities: folding batches sequentially
    and merging independently-folded reservoirs agree BIT-FOR-BIT."""
    a, b = _batch(2), _batch(3)
    empty = reservoir_empty(8, 32)

    def fold(res, batch, tick):
        keys, vals, valid = batch
        return reservoir_extend(res, jnp.asarray(keys), jnp.asarray(vals),
                                jnp.asarray(valid), 5, tick)

    seq = fold(fold(empty, a, 0), b, 1)
    merged = reservoir_merge(fold(empty, a, 0), fold(empty, b, 1))
    for f in ("priority", "values", "n_seen"):
        np.testing.assert_array_equal(np.asarray(getattr(seq, f)),
                                      np.asarray(getattr(merged, f)), f)


def test_reservoir_moments_match_numpy():
    keys, vals, valid = _batch(4, n=200)
    res = reservoir_extend(reservoir_empty(4, 200), jnp.asarray(keys),
                           jnp.asarray(vals), jnp.asarray(valid), 7, 0)
    n, mean, var = reservoir_moments(res)
    sid = np.asarray(hash2(jnp.asarray(keys), 7)) % 4
    for s in range(4):
        v = vals[valid & (sid == s)].astype(np.float64)
        assert float(n[s]) == len(v)
        np.testing.assert_allclose(float(mean[s]), v.mean(), rtol=1e-5)
        np.testing.assert_allclose(float(var[s]), v.var(ddof=1), rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 300), st.integers(1, 300))
def test_reservoir_merge_property(seed, n1, n2):
    rng = np.random.default_rng(seed)
    empty = reservoir_empty(4, 24)

    def fold(res, n, tick):
        keys = rng.integers(0, 50, n).astype(np.uint32)
        vals = rng.normal(0, 1, n).astype(np.float32)
        return keys, vals, reservoir_extend(
            res, jnp.asarray(keys), jnp.asarray(vals),
            jnp.ones(n, bool), 11, tick)

    k1, v1, ra = fold(empty, n1, 0)
    rng2 = np.random.default_rng(seed)        # replay the same draws
    _ = rng2.integers(0, 50, n1), rng2.normal(0, 1, n1)
    k2, v2, seq = fold(ra, n2, 1)
    rb = reservoir_extend(empty, jnp.asarray(k2), jnp.asarray(v2),
                          jnp.ones(n2, bool), 11, 1)
    merged = reservoir_merge(ra, rb)
    np.testing.assert_array_equal(np.asarray(seq.priority),
                                  np.asarray(merged.priority))
    np.testing.assert_array_equal(np.asarray(seq.values),
                                  np.asarray(merged.values))


def test_three_way_strata_and_exact():
    rng = np.random.default_rng(6)
    rels = [_sorted_rel(list(rng.integers(0, 10, 200)), rng)
            for _ in range(3)]
    strata = build_strata(rels, 16)
    got = float(exact_sum_of_sums(rels, strata))
    ks = [np.asarray(r.keys) for r in rels]
    vs = [np.asarray(r.values) for r in rels]
    want = 0.0
    for key in set(ks[0].tolist()):
        segs = [vs[i][ks[i] == key] for i in range(3)]
        if all(len(s) for s in segs):
            n = [len(s) for s in segs]
            want += (segs[0].sum() * n[1] * n[2]
                     + segs[1].sum() * n[0] * n[2]
                     + segs[2].sum() * n[0] * n[1])
    np.testing.assert_allclose(got, want, rtol=1e-4)
