"""TPC-H CUSTOMER ⋈ ORDERS and SUM(o_totalprice + c_acctbal) over it.

A dataset module: a configuration names it (``"dataset": "tpch_co"``) and
the harness finds it as ``bench/datasets/tpch_co.py``.  It gives

* ``generate(config, seed)``: the tables, made from the seed;
* ``relations(tables)``: (keys, values) per input, in the program's order;
* ``QUERY``: the aggregate and join expression the requests ask for;
* ``largest_stratum(tables)``: the most join rows one key has;
* ``reference(tables)``: the answer, in float64, from the join's definition;
* ``joined(tables)``: the value of every join row, in float64 (the
  controls draw from it);
* ``brute_force(tables)``: a nested-loop join, the reference's own check.

The benchmark's own copy of the table generator, so that no change to the
program under test can change the data it is measured on.  The schema and
sizes follow TPC-H clause 4.2.5: 150,000 CUSTOMER rows and 1,500,000 ORDERS
rows per scale factor, keys 1..N.  Two departures from the specification,
recorded as ``assumed`` in the configuration file:

* ``o_custkey`` is drawn uniformly over two thirds of the customers (the
  specification leaves every third customer without orders; which third is
  drawn from the seed here);
* ``o_totalprice`` is uniform on [800, 500000] rather than derived from the
  order's line items (no LINEITEM table is generated).

``c_acctbal`` is uniform on [-999.99, 9999.99], as the specification says.
Values are float32, the type the program stores them in.

The reference: for every key k with n_C(k) customers and n_O(k) orders,

    SUM = sum_k n_C(k) * sum_{o: key k} v_o + n_O(k) * sum_{c: key k} v_c
    COUNT = sum_k n_C(k) * n_O(k)
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from reference import Reference

CUSTOMERS_PER_SF = 150_000
ORDERS_PER_CUSTOMER = 10
QUERY = {"agg": "sum", "expr": "sum"}


class Tables(NamedTuple):
    customer_key: np.ndarray       # uint32 [C], c_custkey
    customer_acctbal: np.ndarray   # float32 [C]
    orders_custkey: np.ndarray     # uint32 [O], o_custkey
    orders_totalprice: np.ndarray  # float32 [O]


def generate(config: dict, seed: int) -> Tables:
    """CUSTOMER and ORDERS at ``config["scale_factor"]`` (1.0 = SF1),
    reproducible from ``seed`` (any non-negative integer, also above 32
    bits)."""
    rng = np.random.default_rng([int(seed), 0])
    n_cust = max(int(CUSTOMERS_PER_SF * float(config["scale_factor"])), 16)
    n_ord = n_cust * ORDERS_PER_CUSTOMER
    cust_key = np.arange(1, n_cust + 1, dtype=np.uint32)
    acctbal = rng.uniform(-999.99, 9999.99, n_cust).astype(np.float32)
    with_orders = rng.choice(cust_key, size=max(2 * n_cust // 3, 1),
                             replace=False)
    ord_cust = rng.choice(with_orders, size=n_ord).astype(np.uint32)
    totalprice = rng.uniform(800.0, 500_000.0, n_ord).astype(np.float32)
    return Tables(cust_key, acctbal, ord_cust, totalprice)


def relations(t: Tables) -> list:
    """ORDERS first, then CUSTOMER: ``expr="sum"`` adds their values."""
    return [(t.orders_custkey, t.orders_totalprice),
            (t.customer_key, t.customer_acctbal)]


def largest_stratum(t: Tables) -> int:
    """The most orders any one customer has (each customer key is
    unique, so that many join rows)."""
    return int(np.bincount(t.orders_custkey).max())


def _key_sums(keys: np.ndarray, values: np.ndarray, size: int):
    """Per-key row counts and float64 value sums over keys < ``size``."""
    k = keys.astype(np.int64)
    return (np.bincount(k, minlength=size).astype(np.int64),
            np.bincount(k, weights=values.astype(np.float64),
                        minlength=size))


def reference(t: Tables) -> Reference:
    size = int(max(t.customer_key.max(), t.orders_custkey.max())) + 1
    n_c, s_c = _key_sums(t.customer_key, t.customer_acctbal, size)
    n_o, s_o = _key_sums(t.orders_custkey, t.orders_totalprice, size)
    total = float(np.sum(n_c * s_o, dtype=np.float64)
                  + np.sum(n_o * s_c, dtype=np.float64))
    return Reference(total, int(np.sum(n_c * n_o)))


def joined(t: Tables) -> np.ndarray:
    """o_totalprice + c_acctbal of every join row (customer keys are
    1..C, one row each)."""
    cust = t.orders_custkey.astype(np.int64) - 1
    return (t.orders_totalprice.astype(np.float64)
            + t.customer_acctbal[cust].astype(np.float64))


def brute_force(t: Tables) -> Reference:
    """Nested-loop join (tiny tables only)."""
    total, count = 0.0, 0
    for kc, vc in zip(t.customer_key.tolist(), t.customer_acctbal.tolist()):
        for ko, vo in zip(t.orders_custkey.tolist(),
                          t.orders_totalprice.tolist()):
            if kc == ko:
                total += float(vo) + float(vc)
                count += 1
    return Reference(total, count)
