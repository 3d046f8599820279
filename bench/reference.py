"""The numbers that decide ``correct``, and the controls those numbers must
fail.

The reference answer itself is the dataset's (``bench/datasets/<name>.py``
``reference``): a float64 aggregate over the benchmark's own tables,
written from the join's definition and independent of the program.

Numbers compared (each beside its limit from the traffic mix):

* ``unanswered``: requests due in the window whose result never came or
  was an error.  Limit 0.
* ``count_mismatch``: answers whose join cardinality differs from COUNT.
  Exact comparison, limit 0.
* ``exact_rel_err_max``: the largest |estimate - SUM| / |SUM| over the
  answers to exact budgets.
* ``budget_ratio_max``: the largest |estimate - SUM| / (e * |SUM|) over
  the answers to error budgets e: at most 1 when every answer lies within
  the relative error its user asked for.
* ``bound_miss_share``: the share of the answers to error budgets whose
  |estimate - SUM| exceeds the error bound the answer reports (a bound of
  0 misses unless the estimate is exact): at the stated confidence c, a
  bound misses on about 1 - c of answers.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import numpy as np


class Reference(NamedTuple):
    total: float     # the aggregate over the join, float64
    count: int       # join cardinality


def numbers(answers: Sequence[Optional[dict]],
            budgets: Sequence[Optional[float]], ref: Reference) -> dict:
    """The compared numbers over every answer due in the window.

    ``answers[i]`` is ``{"estimate", "bound", "count"}`` or None when the
    i-th request never got a result; ``budgets[i]`` is its relative error
    budget (None: exact)."""
    out = {"unanswered": sum(a is None for a in answers),
           "count_mismatch": sum(1 for a in answers if a is not None
                                 and int(round(a["count"])) != ref.count)}
    scale = abs(ref.total)
    exact = [abs(a["estimate"] - ref.total) / scale
             for a, e in zip(answers, budgets) if a is not None and e is None]
    sampled = [(a, e) for a, e in zip(answers, budgets)
               if a is not None and e is not None]
    if exact:
        out["exact_rel_err_max"] = max(exact)
    if sampled:
        out["budget_ratio_max"] = max(abs(a["estimate"] - ref.total)
                                      / (e * scale) for a, e in sampled)
        out["bound_miss_share"] = sum(
            abs(a["estimate"] - ref.total) > a["bound"]
            for a, _ in sampled) / len(sampled)
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number that has a
    limit; a number with no limit is an error in the mix file."""
    checks = {}
    for name, value in nums.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        checks[name] = {"value": value, "limit": limits[name]}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


# ---------------------------------------------------------------------------
# controls: the reference put in the program's place, one step below what
# the configuration states.  ``joined`` is the value of every join row
# (the dataset's ``joined``).
# ---------------------------------------------------------------------------

def control_exact_bf16(joined: np.ndarray) -> dict:
    """Computed on the default device in bfloat16, the precision below
    the float32 the configuration states: the join rows' values and the
    row count are summed as bfloat16 arrays (the device accumulates inside
    the reduction as it chooses; the result is bfloat16)."""
    import jax.numpy as jnp
    v = jnp.asarray(joined.astype(np.float32)).astype(jnp.bfloat16)
    total = jnp.sum(v)
    count = jnp.sum(jnp.ones(v.shape, jnp.bfloat16))
    return {"estimate": float(total), "count": float(count), "bound": 0.0}


def control_sampled(joined: np.ndarray, budget: float, ref: Reference,
                    rng: np.random.Generator, z: float = 1.0) -> dict:
    """For an error budget, drawn at confidence z = 1 (68%) where the
    configuration states 95% (z = 1.96): a uniform with-replacement sample
    of the join's rows, sized so that one standard error equals the
    budget, reporting its one-standard-error bound.  A server tempted to
    draw less than the stated confidence gives answers like these."""
    n_rows = joined.size
    sd = float(np.std(joined))
    n = max(int(math.ceil((z * n_rows * sd / (budget * abs(ref.total)))
                          ** 2)), 1)
    draw = joined[rng.integers(0, n_rows, size=n)]
    return {"estimate": float(n_rows * draw.mean()),
            "bound": float(z * n_rows * draw.std() / math.sqrt(n)),
            "count": float(ref.count)}
