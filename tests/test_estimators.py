"""Estimator correctness: t-quantiles, CLT coverage, Horvitz-Thompson
unbiasedness, distributed-merge equivalence."""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import hypothesis_or_stubs
from repro.core.cost import CostModel, sizes_for_latency
from repro.core.estimators import (HTParts, StratumStats, clt_avg,
                                   clt_avg_from, clt_count, clt_finish,
                                   clt_stdev, clt_stdev_from, clt_sum,
                                   clt_sum_parts, horvitz_thompson_sum,
                                   ht_finish, ht_sum_parts,
                                   inclusion_probability,
                                   second_moment_stats, t_quantile)

given, settings, st = hypothesis_or_stubs()

# two-sided 97.5% t quantiles (scipy.stats.t.ppf(0.975, df))
_T975 = {5: 2.5706, 10: 2.2281, 30: 2.0423, 100: 1.9840, 1000: 1.9623}


def test_t_quantile_known_values():
    for df, want in _T975.items():
        got = float(t_quantile(0.975, df))
        assert abs(got - want) < 2e-2, (df, got, want)
    assert abs(float(t_quantile(0.975, 1e6)) - 1.95996) < 1e-3


def test_t_quantile_monotone_in_confidence():
    df = 20.0
    qs = [float(t_quantile(p, df)) for p in (0.9, 0.95, 0.975, 0.995)]
    assert qs == sorted(qs)


def _stats_from_population(rng, pops, sample_frac):
    """Draw with replacement from synthetic strata; return stats + truth."""
    valid, B, b, sf, sf2 = [], [], [], [], []
    truth = 0.0
    for i, n in enumerate(pops):
        vals = rng.normal(5.0 + i, 1.0 + 0.2 * i, size=n)
        truth += vals.sum()
        k = max(int(n * sample_frac), 2)
        pick = rng.choice(vals, size=k, replace=True)
        valid.append(True)
        B.append(float(n))
        b.append(float(k))
        sf.append(float(pick.sum()))
        sf2.append(float((pick ** 2).sum()))
    stats = StratumStats(jnp.asarray(valid), jnp.asarray(B, jnp.float32),
                         jnp.asarray(b, jnp.float32),
                         jnp.asarray(sf, jnp.float32),
                         jnp.asarray(sf2, jnp.float32))
    return stats, truth


def test_clt_coverage():
    """95% CI covers the truth at roughly the nominal rate."""
    rng = np.random.default_rng(7)
    pops = [50, 200, 1000, 3000]
    hits = 0
    trials = 120
    for _ in range(trials):
        stats, truth = _stats_from_population(rng, pops, 0.1)
        est = clt_sum(stats, 0.95)
        hits += bool(est.lo <= truth <= est.hi)
    assert hits / trials >= 0.85, hits / trials


def test_clt_count_exact():
    stats = StratumStats(jnp.asarray([True, True, False]),
                         jnp.asarray([10.0, 20.0, 99.0]),
                         jnp.asarray([2.0, 2.0, 0.0]),
                         jnp.zeros(3), jnp.zeros(3))
    assert float(clt_count(stats)) == 30.0


def test_parts_merge_equals_direct():
    """psum-style merge of per-shard parts == single-shot estimate."""
    rng = np.random.default_rng(3)
    s1, t1 = _stats_from_population(rng, [100, 500], 0.2)
    s2, t2 = _stats_from_population(rng, [300, 50], 0.2)
    merged = clt_sum_parts(s1)
    p2 = clt_sum_parts(s2)
    merged = type(merged)(*[a + b for a, b in zip(merged, p2)])
    est_merged = clt_finish(merged)
    whole = StratumStats(*[jnp.concatenate([a, b])
                           for a, b in zip(s1, s2)])
    est_whole = clt_sum(whole)
    np.testing.assert_allclose(float(est_merged.estimate),
                               float(est_whole.estimate), rtol=1e-6)
    np.testing.assert_allclose(float(est_merged.error_bound),
                               float(est_whole.error_bound), rtol=1e-5)


def test_inclusion_probability_limits():
    # tiny sample of a huge stratum: pi ~ b/B
    pi = float(inclusion_probability(jnp.asarray(1e6), jnp.asarray(10.0)))
    assert abs(pi - 1e-5) / 1e-5 < 0.01
    # sampling B-with-replacement draws: pi -> 1 - 1/e
    pi = float(inclusion_probability(jnp.asarray(100.0), jnp.asarray(100.0)))
    assert abs(pi - (1 - np.exp(-1))) < 0.01


def test_horvitz_thompson_unbiased():
    """HT over deduplicated draws averages to the truth."""
    rng = np.random.default_rng(11)
    B = 200
    vals = rng.normal(3.0, 1.0, size=B)
    truth = vals.sum()
    ests = []
    for _ in range(200):
        k = 60
        idx = rng.integers(0, B, size=k)
        uniq = np.unique(idx)
        stats = StratumStats(jnp.asarray([True]),
                             jnp.asarray([float(B)]),
                             jnp.asarray([float(k)]),
                             jnp.asarray([0.0]), jnp.asarray([0.0]))
        est = horvitz_thompson_sum(stats,
                                   jnp.asarray([float(vals[uniq].sum())]),
                                   jnp.asarray([float(len(uniq))]))
        ests.append(float(est.estimate))
    assert abs(np.mean(ests) - truth) / abs(truth) < 0.03


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(1, 1e4), min_size=1, max_size=8),
       st.floats(0.01, 1.0))
def test_clt_variance_nonnegative(pops, frac):
    rng = np.random.default_rng(0)
    stats, _ = _stats_from_population(rng, [max(int(p), 3) for p in pops],
                                      max(frac, 0.05))
    est = clt_sum(stats)
    assert float(est.variance) >= 0.0
    assert float(est.error_bound) >= 0.0


def _moment_stats(B, b, mu, sd):
    """Stats with EXACT per-stratum sample moments (mean mu, variance sd^2);
    isolates the estimator's analytic shape from sampling noise."""
    B = np.asarray(B, np.float32)
    b = np.asarray(b, np.float32)
    mu = np.asarray(mu, np.float32)
    sd = np.asarray(sd, np.float32)
    return StratumStats(jnp.asarray(B > 0), jnp.asarray(B), jnp.asarray(b),
                        jnp.asarray(b * mu),
                        jnp.asarray(b * (sd**2 + mu**2)))


@settings(max_examples=30, deadline=None)
@given(st.integers(50, 100_000), st.floats(-50, 50), st.floats(0.1, 20),
       st.integers(2, 30), st.integers(1, 40))
def test_ci_width_shrinks_monotonically_with_sample_size(B, mu, sd, b1, step):
    """More draws at the same sample moments never widen the interval:
    the FPC factor (B-b)/(b-1) and the t quantile both fall with b."""
    b2 = min(b1 + step, B)
    b1 = min(b1, B)
    w1 = float(clt_sum(_moment_stats([B], [b1], [mu], [sd])).error_bound)
    w2 = float(clt_sum(_moment_stats([B], [b2], [mu], [sd])).error_bound)
    assert np.isfinite(w1) and np.isfinite(w2)
    assert w2 <= w1 * (1 + 1e-6), (b1, b2, w1, w2)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 12), st.integers(0, 2**31 - 1))
def test_estimates_invariant_to_stratum_permutation(n_strata, perm_seed):
    """Slot order is an implementation detail (canonical key-sorted [S] vs
    the psum path's concatenated per-device layout): every estimator must
    give the same answer, up to float reassociation of the sums."""
    rng = np.random.default_rng(0)
    pops = list(rng.integers(10, 500, size=n_strata))
    stats, _ = _stats_from_population(rng, pops, 0.2)
    uf = jnp.asarray(rng.normal(5.0, 1.0, n_strata).astype(np.float32))
    uc = jnp.asarray(np.maximum(rng.integers(1, 10, n_strata), 1)
                     .astype(np.float32))
    perm = np.random.default_rng(perm_seed).permutation(n_strata)
    p_stats = StratumStats(*[jnp.asarray(np.asarray(x)[perm])
                             for x in stats])
    for fn, args, pargs in (
            (clt_sum, (stats,), (p_stats,)),
            (clt_avg, (stats,), (p_stats,)),
            (clt_stdev, (stats,), (p_stats,)),
            (horvitz_thompson_sum, (stats, uf, uc),
             (p_stats, uf[perm], uc[perm]))):
        a, b = fn(*args), fn(*pargs)
        np.testing.assert_allclose(float(a.estimate), float(b.estimate),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(a.error_bound),
                                   float(b.error_bound), rtol=1e-4,
                                   atol=1e-5)
        assert float(a.dof) == float(b.dof)


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 10), st.integers(0, 2**31 - 1))
def test_zero_sample_strata_give_finite_bounds(n_strata, seed):
    """Strata that drew nothing (and empty strata) must yield finite — not
    NaN/inf — estimates and bounds from every estimator."""
    rng = np.random.default_rng(seed)
    B = rng.integers(0, 200, n_strata).astype(np.float32)
    b = np.where(rng.random(n_strata) < 0.5, 0.0,
                 rng.integers(0, 5, n_strata)).astype(np.float32)
    b = np.minimum(b, B)
    mu = rng.normal(3.0, 2.0, n_strata).astype(np.float32)
    sd = np.abs(rng.normal(0.0, 2.0, n_strata)).astype(np.float32)
    stats = _moment_stats(B, b, mu, sd)
    uf = jnp.asarray(np.where(b > 0, mu, 0.0).astype(np.float32))
    uc = jnp.asarray(np.minimum(b, 3.0).astype(np.float32))
    for est in (clt_sum(stats), clt_avg(stats), clt_stdev(stats),
                horvitz_thompson_sum(stats, uf, uc)):
        for v in (est.estimate, est.error_bound, est.variance, est.dof):
            assert np.isfinite(float(v)), (est, B, b)


def test_ht_parts_merge_equals_direct():
    """psum-style merge of per-shard HT parts == single-shot HT estimate
    (the psum serve path's dedup estimator)."""
    rng = np.random.default_rng(5)
    s1, _ = _stats_from_population(rng, [100, 400], 0.3)
    s2, _ = _stats_from_population(rng, [250, 60], 0.3)
    ufs = [jnp.asarray(rng.normal(4, 1, 2).astype(np.float32))
           for _ in range(2)]
    ucs = [jnp.asarray(rng.integers(1, 8, 2).astype(np.float32))
           for _ in range(2)]
    p1 = ht_sum_parts(s1, ufs[0], ucs[0])
    p2 = ht_sum_parts(s2, ufs[1], ucs[1])
    merged = ht_finish(HTParts(*[a + b for a, b in zip(p1, p2)]))
    whole = horvitz_thompson_sum(
        StratumStats(*[jnp.concatenate([a, b]) for a, b in zip(s1, s2)]),
        jnp.concatenate(ufs), jnp.concatenate(ucs))
    np.testing.assert_allclose(float(merged.estimate), float(whole.estimate),
                               rtol=1e-6)
    np.testing.assert_allclose(float(merged.error_bound),
                               float(whole.error_bound), rtol=1e-5)


def test_avg_stdev_parts_merge_equals_direct():
    """AVG and STDEV finish from psum'd parts == whole-array estimates."""
    rng = np.random.default_rng(9)
    s1, _ = _stats_from_population(rng, [150, 700], 0.2)
    s2, _ = _stats_from_population(rng, [80, 900], 0.2)
    whole = StratumStats(*[jnp.concatenate([a, b])
                           for a, b in zip(s1, s2)])
    parts = clt_sum_parts(s1)
    parts = type(parts)(*[a + b for a, b in zip(parts, clt_sum_parts(s2))])
    a_merged, a_whole = clt_avg_from(parts), clt_avg(whole)
    np.testing.assert_allclose(float(a_merged.estimate),
                               float(a_whole.estimate), rtol=1e-6)
    tau2 = (clt_sum_parts(second_moment_stats(s1)).tau
            + clt_sum_parts(second_moment_stats(s2)).tau)
    s_merged, s_whole = clt_stdev_from(parts, tau2), clt_stdev(whole)
    np.testing.assert_allclose(float(s_merged.estimate),
                               float(s_whole.estimate), rtol=1e-5)
    np.testing.assert_allclose(float(s_merged.error_bound),
                               float(s_whole.error_bound), rtol=1e-4)


@pytest.mark.xfail(strict=True, reason="one-draw strata report no variance "
                   "(ROADMAP §3)")
def test_single_draw_strata_report_a_bound():
    """A latency budget that affords a small fraction draws once from
    every stratum (``cost.sizes_for_latency``).  The estimate still varies
    from sample to sample, so its 95% bound must not be zero."""
    rng = np.random.default_rng(3)
    pops = np.full(200, 15.0, np.float32)
    b = sizes_for_latency(CostModel(beta_compute=1.0, epsilon=0.0), 30.0,
                          0.0, jnp.asarray(pops))
    assert (np.asarray(b) == 1.0).all()
    vals = rng.normal(100.0, 30.0, size=(len(pops), 15))
    pick = vals[np.arange(len(pops)), rng.integers(0, 15, len(pops))]
    stats = StratumStats(jnp.ones(len(pops), bool), jnp.asarray(pops),
                         jnp.asarray(b), jnp.asarray(pick, jnp.float32),
                         jnp.asarray(pick ** 2, jnp.float32))
    est = clt_sum(stats, 0.95)
    assert float(est.estimate) != float(vals.sum())
    assert float(est.error_bound) > 0
