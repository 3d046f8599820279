"""ApproxJoin — the paper's operator, end to end (single device).

Pipeline (paper Fig. 2/7):

  1. build a Bloom filter per input                         (§3.1, Alg. 1)
  2. AND them into the join filter, probe, drop dead tuples (§3.1)
  3. group surviving tuples into strata (sort + segments)   (§3.3)
  4. decide: exact join affordable? else pick b_i            (§3.1.1, §3.2)
  5. stratified edge-sampling during the join               (§3.3, Alg. 2)
  6. estimate + error bound (CLT or Horvitz-Thompson)       (§3.4)

The orchestration lives in Python (the Spark "driver" role); every stage is a
jittable pure function (the "executor" role).  The distributed version with
identical semantics is ``core/distributed.py`` (shard_map over the mesh).
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bloom
from repro.core.budget import QueryBudget
from repro.core.cost import (CostModel, SigmaRegistry, sizes_for_error,
                             sizes_for_latency)
from repro.core.estimators import (Estimate, StratumStats, clt_avg, clt_count,
                                   clt_stdev, clt_sum, horvitz_thompson_sum)
from repro.core.relation import Relation, sort_by_key
from repro.core.sampling import (SampleResult, Strata, build_strata,
                                 default_f, exact_count, exact_sum_of_products,
                                 exact_sum_of_products_from,
                                 exact_sum_of_sums, exact_sum_of_sums_from,
                                 sample_edges)

TUPLE_BYTES = 8  # uint32 key + float32 value


def filter_exchange_bytes(n: int, fbytes) -> jnp.ndarray:
    """§3.1 filter-exchange transfer model: bytes moved to build + ship the
    join filter for an n-way join.

    The n per-dataset filters travel to the merge site (n transfers) and the
    AND-merged join filter is broadcast back to the workers; as in Spark's
    torrent broadcast the paper charges the broadcast once, not per-worker —
    hence (n + 1) filter-sized transfers for every n >= 2.  The distributed
    engine's all-gather merge (``distributed.py``) restates the same model as
    ``(k - 1) * (n + 1)`` per-device transfers on a k-device mesh: each of
    the n + 1 logical transfers costs (k - 1) device hops.
    """
    return fbytes * (n + 1)


class JoinDiagnostics(NamedTuple):
    total_counts: jnp.ndarray       # [n] tuples per input
    live_counts: jnp.ndarray        # [n] tuples surviving the join filter
    overlap_fraction: jnp.ndarray   # paper §3.1.1 definition
    filter_bytes: int               # |BF| bytes (per filter)
    shuffled_bytes_filtered: jnp.ndarray   # live tuples + filters (ours)
    shuffled_bytes_repartition: jnp.ndarray  # all tuples (baseline model)
    num_strata: jnp.ndarray
    strata_overflow: jnp.ndarray
    total_population: jnp.ndarray   # sum_i B_i (join output size)
    sample_draws: jnp.ndarray       # sum_i b_i actually drawn
    d_filter_s: float               # measured wall time of stage 1-2
    sampled: bool                   # False -> exact path was taken
    dist_dropped_tuples: float = 0.0  # mesh shuffle rows beyond bucket_cap


class JoinResult(NamedTuple):
    estimate: jnp.ndarray
    error_bound: jnp.ndarray
    count: jnp.ndarray              # exact join-output cardinality
    dof: jnp.ndarray
    diagnostics: JoinDiagnostics
    stats: Optional[StratumStats] = None
    strata: Optional[Strata] = None


EXPRS: dict = {
    "sum": (default_f, exact_sum_of_sums),
    "product": (lambda vs: jnp.prod(jnp.stack(vs), axis=0),
                exact_sum_of_products),
}


def build_join_filter(rels: Sequence[Relation], num_blocks: int,
                      seed: int) -> bloom.BloomFilter:
    """Alg. 1: per-input filters, AND-merged into the join filter."""
    filters = [bloom.build(r.keys, r.valid, num_blocks, seed) for r in rels]
    return bloom.intersect_all(filters)


@jax.named_scope("filter_probe")
def filter_relations(rels: Sequence[Relation],
                     join_filter: bloom.BloomFilter) -> list[Relation]:
    """Probe + discard (the shuffle-avoidance step)."""
    return [Relation(r.keys, r.values,
                     r.valid & bloom.contains(join_filter, r.keys))
            for r in rels]


# ---------------------------------------------------------------------------
# Stage functions.  Each is a pure function of arrays + static config, so the
# serving engine (runtime/join_serve.py) can jit(vmap(...)) them across a
# batch of same-shape queries; approx_join below composes the same functions
# eagerly, which keeps the two paths bit-identical by construction.
# ---------------------------------------------------------------------------

class PrepareOut(NamedTuple):
    """Stages 1-3 output: live sorted relations + strata + row counts.

    ``population`` duplicates ``strata.population`` as a plain array: the
    Strata properties reduce over fixed axes, so they cannot be read off a
    *batched* Strata pytree — the serving engine needs the per-example value
    computed inside the vmapped stage.
    """

    sorted_rels: list[Relation]
    strata: Strata
    live_counts: jnp.ndarray   # int32 [n]
    total_counts: jnp.ndarray  # int32 [n]
    population: jnp.ndarray    # f32   [S]


def _prepare_tail(live: Sequence[Relation], rels: Sequence[Relation],
                  max_strata: int) -> PrepareOut:
    """Shared sort/group-by tail of every prepare variant (jnp and kernel,
    single and batched) — one copy, so the bit-parity contract between the
    variants cannot drift."""
    with jax.named_scope("sort"):
        sorted_rels = [sort_by_key(r) for r in live]
    strata = build_strata(sorted_rels, max_strata)
    return PrepareOut(sorted_rels, strata,
                      jnp.stack([r.count() for r in live]),
                      jnp.stack([r.count() for r in rels]),
                      strata.population)


def prepare_stage(rels: Sequence[Relation], num_blocks: int, max_strata: int,
                  seed) -> PrepareOut:
    """Filter build/AND/probe, sort, group-by — one jit/vmap-friendly pass.

    ``seed`` may be a traced array (per-query seeds batch under vmap) —
    :func:`bloom.intersect_all` checks seed equality only on concrete ints,
    so the cascaded AND-merge routes through it on tracers too.
    """
    with jax.named_scope("filter_probe"):
        filters = [bloom.build(r.keys, r.valid, num_blocks, seed)
                   for r in rels]
        join_filter = bloom.intersect_all(filters)
    return _prepare_tail(filter_relations(rels, join_filter), rels,
                         max_strata)


def prepare_stage_pre(rels: Sequence[Relation], filter_words: jnp.ndarray,
                      max_strata: int, seed) -> PrepareOut:
    """:func:`prepare_stage` with PREBUILT per-input filter words.

    ``filter_words`` is ``[n_inputs, num_blocks, W]`` — the packed words of
    each input's dataset filter, e.g. from the JoinServer's per-dataset cache
    (built once per ``(num_blocks, seed)``, reused every step).  Everything
    downstream of the build is identical to :func:`prepare_stage`, so the
    results are bit-identical to building from scratch.
    """
    if filter_words.shape[0] != len(rels):
        raise ValueError(
            f"prepare_stage_pre: {filter_words.shape[0]} prebuilt filters "
            f"for {len(rels)} inputs")
    with jax.named_scope("filter_probe"):
        join_filter = bloom.intersect_all(
            [bloom.BloomFilter(filter_words[i], seed)
             for i in range(filter_words.shape[0])])
    return _prepare_tail(filter_relations(rels, join_filter), rels,
                         max_strata)


def prepare_stage_kernels(rels: Sequence[Relation], num_blocks: int,
                          max_strata: int, seed, *,
                          filter_words: Optional[jnp.ndarray] = None,
                          interpret: bool | None = None) -> PrepareOut:
    """Kernel-backed :func:`prepare_stage` / :func:`prepare_stage_pre`.

    Same stage contract, Pallas execution: per-input filters come from the
    hash kernel + scatter-OR commit (or arrive PREBUILT as ``filter_words``
    ``[n_inputs, num_blocks, W]`` — e.g. the serving engine's per-dataset
    cache), the AND-merge happens on the packed words, and the probe runs
    through the probe kernel.  ``seed`` is the FILTER seed
    and may be a traced array (the engine's decoupled ``filter_seed``);
    results are bit-identical to the jnp stages — the kernels share the
    uint32 hash math (asserted in ``tests/test_kernels.py``).
    """
    from repro.kernels import ops as kops
    if filter_words is not None and filter_words.shape[0] != len(rels):
        raise ValueError(
            f"prepare_stage_kernels: {filter_words.shape[0]} prebuilt "
            f"filters for {len(rels)} inputs")
    with jax.named_scope("filter_probe"):
        if filter_words is None:
            words = bloom.intersect_all(
                [kops.build_filter(r.keys, r.valid, num_blocks, seed,
                                   interpret=interpret) for r in rels]).words
        else:
            words = bloom.intersect_all(
                [bloom.BloomFilter(filter_words[i], seed)
                 for i in range(filter_words.shape[0])]).words
        live = [Relation(r.keys, r.values,
                         r.valid & kops.probe_filter(words, r.keys, seed,
                                                     interpret=interpret))
                for r in rels]
    return _prepare_tail(live, rels, max_strata)


def prepare_stage_kernels_batched(rels: Sequence[Relation],
                                  filter_words: jnp.ndarray,
                                  max_strata: int, seeds, *,
                                  interpret: bool | None = None) -> PrepareOut:
    """Slot-batched kernel prepare: the engine's fused-batch counterpart.

    ``rels`` carry slot-stacked ``[B, N]`` arrays, ``filter_words`` is
    ``[B, n_inputs, num_blocks, W]`` (per-slot prebuilt words — the engine
    always has them, from its per-dataset cache or a streaming window's
    OR-merge), ``seeds`` is uint32 ``[B]``.  The AND-merge and the probe run
    through the stacked-filter kernel over a ``(batch_slot, key_block)``
    grid — NOT vmap: the probe kernel owns the slot dimension — and the
    sort/group-by tail vmaps per slot exactly like the jnp path, so every
    slot is bit-identical to :func:`prepare_stage_kernels` on its own.
    """
    from repro.kernels import ops as kops
    if filter_words.shape[1] != len(rels):
        raise ValueError(
            f"prepare_stage_kernels_batched: {filter_words.shape[1]} "
            f"prebuilt filters for {len(rels)} inputs")
    with jax.named_scope("filter_probe"):
        jwords = bloom.intersect_all(
            [bloom.BloomFilter(filter_words[:, i], seeds)
             for i in range(filter_words.shape[1])]).words
        live = [Relation(r.keys, r.values,
                         r.valid & kops.probe_filter_batched(
                             jwords, r.keys, seeds, interpret=interpret))
                for r in rels]
    return jax.vmap(
        lambda live_i, rels_i: _prepare_tail(live_i, rels_i, max_strata))(
        live, list(rels))


def exact_stage(sorted_rels: Sequence[Relation], strata: Strata, *,
                agg: str, expr: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """§3.1.1 exact fast path: (estimate, count) from sufficient statistics."""
    exact_fn = EXPRS[expr][1]
    est = exact_fn(sorted_rels, strata)
    cnt = exact_count(strata)
    if agg == "count":
        est = cnt
    elif agg == "avg":
        est = est / jnp.maximum(cnt, 1.0)
    return est, cnt


def exact_stage_from_sums(S_k: jnp.ndarray, strata: Strata, *,
                          agg: str, expr: str
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`exact_stage` from per-stratum value sums ``[n, S]``.

    The distributed path computes ``S_k`` per device, merges the owned strata
    into the canonical key-sorted ``[S]`` layout, and finishes here with the
    same arithmetic as the single-device stage — bit-identical results.
    """
    finish = {"sum": exact_sum_of_sums_from,
              "product": exact_sum_of_products_from}[expr]
    est = finish(S_k, strata)
    cnt = exact_count(strata)
    if agg == "count":
        est = cnt
    elif agg == "avg":
        est = est / jnp.maximum(cnt, 1.0)
    return est, cnt


def estimate_stage(sample: SampleResult, *, agg: str, dedup: bool,
                   confidence: float):
    """§3.4: sufficient statistics -> (value, error bound, count, dof)."""
    if dedup:
        est = horvitz_thompson_sum(sample.stats, sample.unique_f,
                                   sample.unique_count, confidence)
    elif agg == "avg":
        est = clt_avg(sample.stats, confidence)
    elif agg == "stdev":
        est = clt_stdev(sample.stats, confidence)
    else:
        est = clt_sum(sample.stats, confidence)
    cnt = clt_count(sample.stats)
    value = cnt if agg == "count" else est.estimate
    err = jnp.zeros_like(est.error_bound) if agg == "count" \
        else est.error_bound
    return value, err, cnt, est.dof


def sample_stage(sorted_rels: Sequence[Relation], strata: Strata,
                 b_i: jnp.ndarray, b_max: int, seed, *,
                 agg: str = "sum", dedup: bool = False,
                 confidence: float = 0.95,
                 f_fn: Callable = None):
    """Stages 4-6 (sampled path): draw + aggregate + error bound."""
    sample = sample_edges(sorted_rels, strata, b_i, b_max, seed,
                          default_f if f_fn is None else f_fn)
    value, err, cnt, dof = estimate_stage(sample, agg=agg, dedup=dedup,
                                          confidence=confidence)
    return value, err, cnt, dof, sample.stats


def _kernel_sample_result(stats: StratumStats) -> SampleResult:
    """Wrap kernel StratumStats as a SampleResult (non-dedup: the HT/dedup
    fields are unused by :func:`estimate_stage`, stubbed to zeros)."""
    zeros = stats.sum_f * 0
    return SampleResult(stats, zeros, zeros,
                        jnp.zeros((1, 1)), jnp.zeros((1, 1), bool))


def sample_stage_kernels(sorted_rels: Sequence[Relation], strata: Strata,
                         b_i: jnp.ndarray, b_max: int, seed, *,
                         agg: str = "sum", confidence: float = 0.95,
                         expr: str = "sum",
                         interpret: bool | None = None):
    """Kernel-backed :func:`sample_stage` (two-way, non-dedup): the fused
    draw->gather->f->reduce Pallas sampler + the shared estimate stage."""
    from repro.kernels import ops as kops
    with jax.named_scope("sampler"):
        stats = kops.sample_stats(sorted_rels, strata, b_i, b_max, seed,
                                  expr, interpret=interpret)
    value, err, cnt, dof = estimate_stage(
        _kernel_sample_result(stats), agg=agg, dedup=False,
        confidence=confidence)
    return value, err, cnt, dof, stats


def sample_stage_kernels_batched(sorted_rels: Sequence[Relation],
                                 strata: Strata, b_i: jnp.ndarray,
                                 b_max: int, seeds, *,
                                 agg: str = "sum", confidence: float = 0.95,
                                 expr: str = "sum",
                                 interpret: bool | None = None):
    """Slot-batched kernel sample stage (engine counterpart).

    Inputs are slot-stacked (``[B, ...]`` leaves, as emitted by the batched
    prepare); the fused sampler runs the ``(batch_slot, strata_block)``
    kernel grid directly — the slot dimension belongs to the kernel, not
    vmap — and the estimator finish vmaps per slot.  The batched Strata
    pytree's reducing properties (``joinable``/``population``) cannot be
    read off batched leaves, so they are recomputed here over the per-slot
    axes (same arithmetic, one axis over).
    """
    from repro.kernels import ops as kops
    joinable = strata.valid & jnp.all(strata.counts > 0, axis=1)
    population = jnp.where(
        joinable,
        jnp.prod(jnp.maximum(strata.counts, 0).astype(jnp.float32), axis=1),
        0.0)
    with jax.named_scope("sampler"):
        stats = kops.sample_stats_batched(
            sorted_rels[0].values, sorted_rels[1].values,
            strata.keys, strata.starts, strata.counts, joinable, population,
            b_i, seeds, b_max, expr, interpret=interpret)
    value, err, cnt, dof = jax.vmap(
        lambda s: estimate_stage(_kernel_sample_result(s), agg=agg,
                                 dedup=False, confidence=confidence))(stats)
    return value, err, cnt, dof, stats


def _pilot_sizes(population, fraction: float) -> jnp.ndarray:
    b = jnp.ceil(fraction * jnp.asarray(population, jnp.float32))
    return jnp.where(jnp.asarray(population) > 0, jnp.maximum(b, 1.0), 0.0)


def decide_sample_sizes(budget: QueryBudget, strata: Strata,
                        cost_model: Optional[CostModel], d_dt: float,
                        sigma: Optional[np.ndarray],
                        confidence: float) -> jnp.ndarray:
    """§3.2: budget -> per-stratum b_i.  Latency and error combine by min."""
    population = strata.population
    b = None
    if budget.error is not None:
        if sigma is not None:
            b = sizes_for_error(budget.error, sigma, population, confidence)
        else:  # first execution: pilot run at a fixed fraction (§3.2-II)
            b = _pilot_sizes(population, budget.pilot_fraction)
    if budget.latency_s is not None:
        assert cost_model is not None, "latency budget needs a CostModel"
        bl = sizes_for_latency(cost_model, budget.latency_s, d_dt, population)
        b = bl if b is None else jnp.minimum(b, bl)
    assert b is not None
    return b


def measured_sigma(stats: StratumStats) -> jnp.ndarray:
    """Per-stratum sigma estimate fed back into the SigmaRegistry."""
    b = jnp.maximum(stats.n_sampled, 1.0)
    r2 = (stats.sum_f2 - stats.sum_f**2 / b) / jnp.maximum(b - 1.0, 1.0)
    return jnp.sqrt(jnp.maximum(r2, 0.0))


def approx_join(rels: Sequence[Relation],
                budget: QueryBudget = QueryBudget(),
                *,
                agg: str = "sum",
                expr: str = "sum",
                f: Optional[Callable] = None,
                seed: int = 0,
                fp_rate: float = 0.01,
                max_strata: Optional[int] = None,
                b_max: Optional[int] = 2048,
                cost_model: Optional[CostModel] = None,
                sigma_registry: Optional[SigmaRegistry] = None,
                query_id: str = "q0",
                dedup: bool = False,
                use_kernels: bool = False) -> JoinResult:
    """The paper's approxjoin() (§4): join + aggregate within a budget.

    ``expr`` selects f over joined values ('sum' -> v1+...+vn); ``agg`` is the
    outer aggregate ('sum' | 'count' | 'avg').  ``dedup=True`` removes
    duplicate edges and switches to the Horvitz-Thompson estimator.
    ``use_kernels=True`` routes filter build/probe and the (two-way,
    non-dedup) sampler through the Pallas kernels (kernels/ops.py) —
    bit-identical results; Mosaic-compiled kernels on a TPU.
    """
    f_fn, exact_fn = EXPRS[expr] if f is None else (f, None)
    n = len(rels)
    max_n = max(r.capacity for r in rels)
    # size the strata grid from the LARGEST input: keyed on rels[0] alone, a
    # join whose later relation is bigger under-sizes S and silently inflates
    # strata_overflow (the overflowing keys fall out of the sample frame)
    S = max_strata or max_n

    # --- stage 1: filtering (timed: feeds d_dt in the latency cost fn) ---
    t0 = time.perf_counter()
    num_blocks = bloom.num_blocks_for(max_n, fp_rate)
    if use_kernels:
        prep = prepare_stage_kernels(rels, num_blocks, S, seed)
    else:
        prep = prepare_stage(rels, num_blocks, S, seed)
    sorted_rels, strata = prep.sorted_rels, prep.strata
    live_counts, total_counts = prep.live_counts, prep.total_counts
    jax.block_until_ready(strata.counts)
    d_filter = time.perf_counter() - t0

    population = strata.population
    total_pop = jnp.sum(population)
    overlap = jnp.sum(live_counts) / jnp.maximum(jnp.sum(total_counts), 1)
    fbytes = num_blocks * bloom.WORDS_PER_BLOCK * 4
    diag = dict(
        total_counts=total_counts, live_counts=live_counts,
        overlap_fraction=overlap, filter_bytes=fbytes,
        shuffled_bytes_filtered=jnp.sum(live_counts) * TUPLE_BYTES
        + filter_exchange_bytes(n, fbytes),
        shuffled_bytes_repartition=jnp.sum(total_counts) * TUPLE_BYTES,
        num_strata=strata.num_strata, strata_overflow=strata.overflow,
        total_population=total_pop, d_filter_s=d_filter,
    )

    # --- stage 2: exact fast path (§3.1.1 "is filtering sufficient?") ---
    exact_affordable = budget.is_exact or (
        budget.latency_s is not None and cost_model is not None
        and exact_fn is not None
        and float(cost_model.beta_compute) * float(total_pop)
        + cost_model.epsilon + d_filter <= budget.latency_s
        and budget.error is None)
    if exact_affordable:
        assert exact_fn is not None, "exact path needs a separable expr"
        est, cnt = exact_stage(sorted_rels, strata, agg=agg, expr=expr)
        return JoinResult(est, jnp.zeros(()), cnt, jnp.zeros(()),
                          JoinDiagnostics(sample_draws=jnp.zeros(()),
                                          sampled=False, **diag),
                          strata=strata)

    # --- stage 3: budget -> b_i (§3.2) ---
    sigma = None
    if (budget.error is not None and sigma_registry is not None
            and sigma_registry.has(query_id)):
        keys = np.asarray(jax.device_get(strata.keys))
        sigma = sigma_registry.lookup(query_id, keys)
    b_i = decide_sample_sizes(budget, strata, cost_model, d_filter, sigma,
                              budget.confidence)
    if b_max is None:
        # adaptive grid: the driver sizes the static [S, b_max] draw grid
        # from the budget (pow2-bucketed to bound recompiles).  Without
        # this, latency is flat in b_i and the latency cost function can't
        # steer (found via the Fig-11 fidelity bench; see EXPERIMENTS.md).
        peak = int(jax.device_get(jnp.max(b_i)))
        b_max = max(64, 1 << (min(peak, 8192) - 1).bit_length())

    # --- stage 4+5: sample during join + estimate (§3.3, §3.4) ---
    if use_kernels and not dedup and n == 2 and f is None:
        value, err, cnt, dof, kstats = sample_stage_kernels(
            sorted_rels, strata, b_i, b_max, seed + 1, agg=agg,
            confidence=budget.confidence, expr=expr)
        sample = _kernel_sample_result(kstats)
    else:
        sample = sample_edges(sorted_rels, strata, b_i, b_max, seed + 1, f_fn)
        value, err, cnt, dof = estimate_stage(sample, agg=agg, dedup=dedup,
                                              confidence=budget.confidence)

    # --- feedback: store measured sigma for the next execution (§3.2-II) ---
    if sigma_registry is not None:
        sig = np.asarray(jax.device_get(measured_sigma(sample.stats)))
        keys = np.asarray(jax.device_get(strata.keys))
        ok = np.asarray(jax.device_get(sample.stats.valid
                                       & (sample.stats.n_sampled > 1)))
        sigma_registry.update(query_id, keys, sig, ok)

    return JoinResult(value, err, cnt, dof,
                      JoinDiagnostics(
                          sample_draws=jnp.sum(sample.stats.n_sampled),
                          sampled=True, **diag),
                      stats=sample.stats, strata=strata)
