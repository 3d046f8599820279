"""The float64 reference against a brute-force join, and the controls
against the limits they must fail."""

import numpy as np

import reference
from cells import BENCH, load_dataset
from traffic import Mix

tpch = load_dataset("tpch_co")
EXACT = Mix.load(BENCH / "traffic" / "exact_closed.json").checks


def _tables(scale, seed):
    return tpch.generate({"scale_factor": scale}, seed)


def test_reference_matches_brute_force():
    t = _tables(0.0002, 99)     # 30 customers, 300 orders
    ref, brute = tpch.reference(t), tpch.brute_force(t)
    assert ref.count == brute.count == 300
    assert abs(ref.total - brute.total) <= 1e-9 * abs(brute.total)
    joined = tpch.joined(t)
    assert joined.size == ref.count
    assert abs(joined.sum() - ref.total) <= 1e-9 * abs(ref.total)


def test_reference_many_to_many():
    # duplicate keys on both sides: every pair joins
    t = tpch.Tables(np.array([1, 1, 2], np.uint32),
               np.array([1.0, 2.0, 4.0], np.float32),
               np.array([1, 2, 2, 3], np.uint32),
               np.array([10.0, 20.0, 30.0, 40.0], np.float32))
    ref, brute = tpch.reference(t), tpch.brute_force(t)
    assert ref.count == brute.count == 4
    assert ref.total == brute.total == (10 + 1) + (10 + 2) + (20 + 4) + (30 + 4)


def test_numbers_and_verdict():
    ref = reference.Reference(100.0, 10)
    answers = [{"estimate": 100.0, "count": 10.0, "bound": 0.0},
               {"estimate": 101.0, "count": 10.0, "bound": 1.5},
               None,
               {"estimate": 99.0, "count": 9.0, "bound": 0.0},
               {"estimate": 100.0, "count": 10.0, "bound": 0.0}]
    budgets = [None, 0.02, None, 0.005, 0.01]
    n = reference.numbers(answers, budgets, ref)
    assert n["unanswered"] == 1 and n["count_mismatch"] == 1
    assert n["exact_rel_err_max"] == 0.0
    assert n["budget_ratio_max"] == 2.0
    # a bound of 0 misses unless the estimate is exact
    assert n["bound_miss_share"] == 1 / 3
    limits = {"unanswered": 0, "count_mismatch": 0, "exact_rel_err_max": 1e-6,
              "budget_ratio_max": 1.0, "bound_miss_share": 0.1}
    ok, checks = reference.verdict(n, limits)
    assert not ok and checks["budget_ratio_max"] == {"value": 2.0,
                                                     "limit": 1.0}


def test_exact_control_fails_the_exact_limits():
    t = _tables(0.05, 3)
    ref = tpch.reference(t)
    a = reference.control_exact_bf16(tpch.joined(t))
    n = reference.numbers([a] * 4, [None] * 4, ref)
    assert n["exact_rel_err_max"] > EXACT["exact_rel_err_max"]
    assert n["count_mismatch"] > EXACT["count_mismatch"]


def test_sampled_control_fails_budget_and_bound():
    t = _tables(0.05, 4)
    ref = tpch.reference(t)
    rng = np.random.default_rng(5)
    budgets = [0.01] * 100
    answers = [reference.control_sampled(tpch.joined(t), e, ref, rng)
               for e in budgets]
    n = reference.numbers(answers, budgets, ref)
    assert n["count_mismatch"] == 0
    # drawn at 68%: about a third of the answers lie beyond the budget and
    # beyond the bound they report
    assert n["budget_ratio_max"] > 1.0
    assert 0.15 < n["bound_miss_share"] < 0.5
