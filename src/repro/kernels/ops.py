"""jit'd public wrappers for the Pallas kernels.

ALL padding lives here: the raw kernels in ``bloom_build``/``bloom_probe``/
``edge_sample`` hard-assert block-multiple shapes, and every wrapper pads its
operands up to those multiples and truncates the results back — so padded
tail keys/strata can never flip a result (property-tested for pow2 and
non-pow2 lengths in ``tests/test_kernels.py``).  The wrappers also handle
the scatter-OR commit for the build kernel and StratumStats assembly for the
sampler.  ``interpret=None`` follows the backend (``kernels.use_interpret``):
Mosaic-compiled on a TPU, Pallas interpret mode anywhere else.

Seeds are RUNTIME ARRAY OPERANDS throughout — never static jit arguments —
so one compiled executable per shape class serves every seed (N distinct
seeds used to cost N compiles; now they cost one, asserted in the tests and
``serve_bench --kernels``).  Each ``*_batched`` wrapper takes slot-stacked
inputs with a leading batch dimension and a ``[B]`` seed vector, matching
the serving engine's fused-batch layout; the single-query wrappers are the
``B = 1`` specialization of the same kernels.

Every wrapper has a pure-jnp oracle in ``kernels/ref.py`` and the swap is
tested bit-exact.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bloom
from repro.core.estimators import StratumStats
from repro.core.relation import Relation
from repro.core.sampling import Strata
from repro.kernels import bloom_build as _build
from repro.kernels import use_interpret  # noqa: F401 — re-exported
from repro.kernels import bloom_probe as _probe
from repro.kernels import edge_sample as _edge


def _pad1(x: jnp.ndarray, mult: int, fill=0):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate([x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])


def _pad2(x: jnp.ndarray, mult: int, fill=0):
    """Pad axis 1 (the per-slot axis of a slot-stacked operand)."""
    n = x.shape[1]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full(x.shape[:1] + (pad,) + x.shape[2:], fill, x.dtype)],
        axis=1)


def _seedvec(seed) -> jnp.ndarray:
    """Seed -> uint32 [1] runtime operand.  Host ints wrap mod 2^32 HERE
    (before jit tracing, which would overflow on ints >= 2^31); traced
    arrays pass straight through."""
    if isinstance(seed, (int, np.integer)):
        seed = np.uint32(int(seed) & 0xFFFFFFFF)
    return jnp.asarray(seed, jnp.uint32).reshape(1)


# ---------------------------------------------------------------------------
# Filter build
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_blocks", "interpret"))
def build_filter_batched(keys: jnp.ndarray, valid: jnp.ndarray,
                         num_blocks: int, seeds: jnp.ndarray,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Kernel-backed per-slot bloom build: packed words uint32 [B, nb, 8].

    ``keys``/``valid`` are slot-stacked ``[B, N]``; ``seeds`` uint32 ``[B]``
    runtime operands (zero recompiles across seeds).
    """
    n = keys.shape[1]
    kp = _pad2(keys, _build.DEFAULT_BLOCK)
    blk, masks = _build.bloom_hashes_batched(kp, seeds, num_blocks,
                                             interpret=interpret)
    commit = jax.vmap(
        lambda b, m, v: bloom.scatter_or(b, m, v, num_blocks).words)
    return commit(blk[:, :n], masks[:, :n], valid)


def build_filter(keys: jnp.ndarray, valid: jnp.ndarray, num_blocks: int,
                 seed=0, interpret: bool | None = None) -> bloom.BloomFilter:
    """Kernel-backed bloom.build: hash kernel + XLA scatter-OR commit.

    Unjitted shim over the jitted batched kernel (B = 1): the seed
    normalizes to a uint32 operand HERE, outside any trace, so host ints of
    any magnitude work and jit callers can pass traced seeds through.
    """
    words = build_filter_batched(keys[None], valid[None], num_blocks,
                                 _seedvec(seed), interpret=interpret)[0]
    return bloom.BloomFilter(words, seed)


# ---------------------------------------------------------------------------
# Filter probe
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def probe_filter_batched(words: jnp.ndarray, keys: jnp.ndarray,
                         seeds: jnp.ndarray,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Kernel-backed per-slot membership probe: bool [B, N].

    ``words`` is the stacked ``[B, nb, 8]`` filter layout (each slot probes
    its OWN filter — the engine's mixed-tenant batch), keys ``[B, N]``,
    ``seeds`` uint32 ``[B]``.
    """
    n = keys.shape[1]
    kp = _pad2(keys, _probe.DEFAULT_BLOCK)
    return _probe.bloom_probe_batched(words, kp, seeds,
                                      interpret=interpret)[:, :n]


def probe_filter(words: jnp.ndarray, keys: jnp.ndarray, seed=0,
                 interpret: bool | None = None) -> jnp.ndarray:
    """Kernel-backed bloom.contains (unjitted B = 1 shim, see build_filter)."""
    return probe_filter_batched(words[None], keys[None], _seedvec(seed),
                                interpret=interpret)[0]


# ---------------------------------------------------------------------------
# Fused edge sampler
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("b_max", "expr", "interpret"))
def sample_stats_batched(values1: jnp.ndarray, values2: jnp.ndarray,
                         strata_keys: jnp.ndarray,
                         starts: jnp.ndarray, counts: jnp.ndarray,
                         joinable: jnp.ndarray, population: jnp.ndarray,
                         b_i: jnp.ndarray, seeds: jnp.ndarray, b_max: int,
                         expr: str = "sum",
                         interpret: bool | None = None) -> StratumStats:
    """Kernel-backed per-slot Algorithm-2 pass: StratumStats with [B, S]
    leaves.  ``starts``/``counts`` are ``[B, 2, S]``; ``seeds`` uint32 [B]."""
    S = strata_keys.shape[1]
    pad = functools.partial(_pad2, mult=_edge.S_BLOCK)
    n, sf, sf2 = _edge.edge_sample_batched(
        values1, values2,
        pad(strata_keys), pad(starts[:, 0]), pad(counts[:, 0]),
        pad(starts[:, 1]), pad(counts[:, 1]),
        pad(joinable), pad(b_i.astype(jnp.float32)),
        seeds, b_max, expr, interpret=interpret)
    return StratumStats(valid=joinable, population=population,
                        n_sampled=n[:, :S], sum_f=sf[:, :S],
                        sum_f2=sf2[:, :S])


def sample_stats_2way(values1: jnp.ndarray, values2: jnp.ndarray,
                      strata_keys: jnp.ndarray,
                      starts: jnp.ndarray, counts: jnp.ndarray,
                      joinable: jnp.ndarray, population: jnp.ndarray,
                      b_i: jnp.ndarray, b_max: int, seed=0,
                      expr: str = "sum",
                      interpret: bool | None = None) -> StratumStats:
    """Kernel-backed two-way Algorithm-2 pass returning StratumStats
    (unjitted B = 1 shim, see build_filter)."""
    stats = sample_stats_batched(
        values1[None], values2[None], strata_keys[None], starts[None],
        counts[None], joinable[None], population[None], b_i[None],
        _seedvec(seed), b_max, expr, interpret=interpret)
    return jax.tree_util.tree_map(lambda x: x[0], stats)


def sample_stats(sorted_rels: Sequence[Relation], strata: Strata,
                 b_i: jnp.ndarray, b_max: int, seed=0,
                 expr: str = "sum", interpret: bool | None = None) -> StratumStats:
    """Convenience: Strata-level entry point (two-way only)."""
    assert len(sorted_rels) == 2, "kernel path is two-way; use core.sampling"
    return sample_stats_2way(
        sorted_rels[0].values, sorted_rels[1].values,
        strata.keys, strata.starts, strata.counts,
        strata.joinable, strata.population,
        b_i, b_max, seed, expr, interpret)
