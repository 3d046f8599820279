"""The table and traffic generators reproduce from a seed, and every seed
gets the same work in another order."""

import numpy as np

import traffic
from cells import load_dataset
from traffic import Mix

tpch = load_dataset("tpch_co")

BIG_SEED = (1 << 31) + 12345     # seeds go past 32 signed bits


def test_tables_reproduce_from_seed():
    cfg = {"scale_factor": 0.01}
    a, b = tpch.generate(cfg, BIG_SEED), tpch.generate(cfg, BIG_SEED)
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    c = tpch.generate(cfg, BIG_SEED + 1)
    assert not np.array_equal(a.orders_custkey, c.orders_custkey)


def test_tables_follow_the_schema():
    t = tpch.generate({"scale_factor": 0.01}, 7)
    assert len(t.customer_key) == 1500 and len(t.orders_custkey) == 15000
    assert np.array_equal(t.customer_key, np.arange(1, 1501))
    # two thirds of the customers have orders; every order has a customer
    assert len(np.unique(t.orders_custkey)) <= 1000
    assert t.orders_custkey.min() >= 1 and t.orders_custkey.max() <= 1500
    assert -999.99 <= t.customer_acctbal.min()
    assert t.customer_acctbal.max() <= 9999.99
    assert 800.0 <= t.orders_totalprice.min()
    assert t.orders_totalprice.max() <= 500_000.0
    assert tpch.largest_stratum(t) >= 10
    (ok, ov), (ck, cv) = tpch.relations(t)
    assert ok is t.orders_custkey and cv is t.customer_acctbal


def _mix(**kw):
    d = dict(name="m", loop="open", query_ids=32, zipf_s=1.1,
             budgets=(0.005, 0.01, 0.02, 0.05), confidence=0.95,
             rate_qps=3.0, clients=None, drain_s=1.0, checks={})
    d.update(kw)
    return Mix(**d)


def test_arrivals_same_gaps_other_order():
    r = lambda s: np.random.default_rng([s, 2])   # noqa: E731
    a = traffic.arrival_offsets(3.0, 40.0, r(BIG_SEED))
    b = traffic.arrival_offsets(3.0, 40.0, r(BIG_SEED))
    c = traffic.arrival_offsets(3.0, 40.0, r(5))
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert abs(len(a) - len(c)) <= 1
    assert abs(len(a) - 120) <= 2 and abs(len(c) - 120) <= 2
    assert a.min() >= 0 and a.max() < 40.0


def test_ids_same_multiset_other_order():
    m = _mix()
    a = traffic.id_sequence(m, 200, np.random.default_rng(1))
    b = traffic.id_sequence(m, 200, np.random.default_rng(2))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    counts = traffic.zipf_counts(200, 32, 1.1)
    assert counts.sum() == 200 and list(counts) == sorted(counts)[::-1]
    assert m.budget_of(0) == 0.005 and m.budget_of(5) == 0.01
    assert _mix(budgets=(None,)).budget_of(3) is None
    mixed = _mix(budgets=(None, 0.02))
    assert mixed.budget_of(4) is None and mixed.budget_of(7) == 0.02


def test_bursts_keep_the_mean_rate():
    rng = lambda: np.random.default_rng(9)   # noqa: E731
    one = traffic.arrival_offsets(3.0, 40.0, rng())
    four = traffic.arrival_offsets(3.0, 40.0, rng(), burst=4)
    assert abs(len(four) - len(one)) <= 4 and len(four) % 4 == 0
    times, counts = np.unique(four, return_counts=True)
    assert set(counts) == {4} and times.max() < 40.0


def test_mix_file_budgets(tmp_path):
    p = tmp_path / "mixed.json"
    p.write_text('{"loop": "open", "rate_qps": 1.0, "query_ids": 4, '
                 '"zipf_s": 1.1, "budgets": [null, 0.01], "burst": 3, '
                 '"schedule_seed": 2, "checks": {"unanswered": 0}}')
    m = Mix.load(p)
    assert m.budgets == (None, 0.01) and m.burst == 3 and m.name == "mixed"


def test_request_seeds_fit_the_program():
    s = traffic.request_seeds(1000, np.random.default_rng(BIG_SEED))
    assert s.min() >= 1 and s.max() < traffic.SEED_BOUND
