"""Pallas kernel sweeps: shapes x seeds x fp-rates, bit-exact vs the ref.py
oracles (interpret mode on CPU; same code Mosaic-compiles on TPU), plus the
batched-slot contracts: the 2-D (batch_slot, key/strata block) grids must be
bit-exact per slot against the single-query wrappers, seeds must be runtime
operands (one compile per shape class across any number of seeds), and
wrapper padding must never flip a result."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stubs
from repro.core import bloom
from repro.core.relation import relation, sort_by_key
from repro.core.sampling import build_strata, sample_edges
from repro.kernels import ops, ref
from repro.kernels.bloom_build import bloom_hashes
from repro.kernels.bloom_probe import bloom_probe
from repro.kernels.edge_sample import edge_sample

given, settings, st = hypothesis_or_stubs()


@pytest.mark.parametrize("n", [2048, 4096, 8192])
@pytest.mark.parametrize("seed", [0, 7])
def test_bloom_hashes_sweep(n, seed):
    keys = jnp.asarray(np.random.default_rng(seed).integers(
        0, 2**32 - 1, n, dtype=np.uint32))
    nb = bloom.num_blocks_for(n, 0.01)
    blk, masks = bloom_hashes(keys, nb, seed, interpret=True)
    rblk, rmasks = ref.bloom_hashes_ref(keys, nb, seed)
    np.testing.assert_array_equal(np.asarray(blk), np.asarray(rblk))
    np.testing.assert_array_equal(np.asarray(masks), np.asarray(rmasks))


@pytest.mark.parametrize("n,fp", [(2048, 0.1), (4096, 0.01), (2048, 0.001)])
def test_bloom_probe_sweep(n, fp):
    rng = np.random.default_rng(n)
    keys = jnp.asarray(rng.integers(0, 1 << 20, n, dtype=np.uint32))
    nb = bloom.num_blocks_for(n, fp)
    f = bloom.build(keys, jnp.ones(n, bool), nb, seed=3)
    probe = jnp.asarray(rng.integers(0, 1 << 21, 4096, dtype=np.uint32))
    got = bloom_probe(f.words, probe, seed=3, interpret=True)
    want = ref.bloom_probe_ref(f.words, probe, seed=3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_build_filter_wrapper_pads_and_matches():
    rng = np.random.default_rng(1)
    for n in (100, 2048, 5000):  # non-multiples exercise padding
        keys = jnp.asarray(rng.integers(0, 1 << 16, n, dtype=np.uint32))
        valid = jnp.asarray(rng.random(n) > 0.2)
        nb = bloom.num_blocks_for(n, 0.01)
        a = bloom.build(keys, valid, nb, seed=5)
        b = ops.build_filter(keys, valid, nb, seed=5, interpret=True)
        np.testing.assert_array_equal(np.asarray(a.words),
                                      np.asarray(b.words))
        m1 = bloom.contains(a, keys)
        m2 = ops.probe_filter(a.words, keys, seed=5, interpret=True)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(m2))


@pytest.mark.parametrize("S,b_max", [(128, 64), (256, 128), (384, 256)])
@pytest.mark.parametrize("expr", ["sum", "product"])
def test_edge_sample_sweep(S, b_max, expr):
    rng = np.random.default_rng(S + b_max)
    n = 4096
    r1 = sort_by_key(relation(
        rng.integers(0, S // 2, n).astype(np.uint32),
        rng.normal(3, 1, n).astype(np.float32)))
    r2 = sort_by_key(relation(
        rng.integers(S // 4, S, n).astype(np.uint32),
        rng.normal(1, 2, n).astype(np.float32)))
    strata = build_strata([r1, r2], S)
    b_i = jnp.ceil(0.3 * strata.population)
    got = edge_sample(r1.values, r2.values, strata.keys,
                      strata.starts[0], strata.counts[0],
                      strata.starts[1], strata.counts[1],
                      strata.joinable, b_i.astype(jnp.float32),
                      b_max, seed=11, expr=expr, interpret=True)
    want = ref.edge_sample_ref(r1.values, r2.values, strata.keys,
                               strata.starts[0], strata.counts[0],
                               strata.starts[1], strata.counts[1],
                               strata.joinable, b_i.astype(jnp.float32),
                               b_max, seed=11, expr=expr)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-4)


def test_edge_sample_matches_core_sampler():
    """Kernel == the full core sampler (which also does dedup bookkeeping)."""
    rng = np.random.default_rng(9)
    n = 2048
    r1 = sort_by_key(relation(rng.integers(0, 40, n).astype(np.uint32),
                              rng.normal(0, 1, n).astype(np.float32)))
    r2 = sort_by_key(relation(rng.integers(20, 60, n).astype(np.uint32),
                              rng.normal(0, 1, n).astype(np.float32)))
    strata = build_strata([r1, r2], 128)
    b_i = jnp.minimum(strata.population, 100.0)
    core = sample_edges([r1, r2], strata, b_i, 128, seed=4)
    kern = ops.sample_stats([r1, r2], strata, b_i, 128, seed=4,
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(core.stats.n_sampled),
                                  np.asarray(kern.n_sampled))
    np.testing.assert_allclose(np.asarray(core.stats.sum_f),
                               np.asarray(kern.sum_f), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(core.stats.sum_f2),
                               np.asarray(kern.sum_f2), rtol=1e-6)


def test_vmem_guards():
    """The sampler sizes its scoped VMEM the way Mosaic counts it — lanes
    padded to 128, slot rows padded to 8 sublanes, double buffering — and
    refuses draw tiles beyond the ceiling.  Filters no longer live in VMEM:
    a filter far larger than VMEM probes fine, because the gather runs in
    XLA over HBM."""
    from repro.kernels import edge_sample as es
    assert es.vmem_bytes(1, 1) == es.vmem_bytes(128, 1)
    assert es.vmem_bytes(128, 1) == es.vmem_bytes(128, 8) \
        < es.vmem_bytes(128, 9)
    assert es.vmem_bytes(2048, 4) <= es.VMEM_LIMIT
    vals = jnp.zeros((4096,), jnp.float32)
    with pytest.raises(AssertionError):     # [128, 2^16] draw tiles
        edge_sample(vals, vals, jnp.zeros((128,), jnp.uint32),
                    jnp.zeros((128,), jnp.int32), jnp.ones((128,), jnp.int32),
                    jnp.zeros((128,), jnp.int32), jnp.ones((128,), jnp.int32),
                    jnp.ones((128,), bool), jnp.ones((128,), jnp.float32),
                    1 << 16)
    big = jnp.zeros((1 << 19, 8), jnp.uint32)      # a 16 MiB filter
    assert not bool(jnp.any(bloom_probe(big, jnp.arange(2048,
                                                        dtype=jnp.uint32))))


# ---------------------------------------------------------------------------
# Batched slot layouts: per-slot bit-parity with the single-query wrappers,
# mixed seeds per slot.
# ---------------------------------------------------------------------------

def test_batched_build_and_probe_mixed_seeds_bit_exact():
    """One stacked dispatch over B slots with B different seeds must equal B
    single-slot calls (and the jnp reference) bit for bit."""
    rng = np.random.default_rng(2)
    B, n = 4, 2048
    keys = jnp.asarray(rng.integers(0, 1 << 20, (B, n), dtype=np.uint32))
    valid = jnp.asarray(rng.random((B, n)) > 0.2)
    probe_keys = jnp.asarray(rng.integers(0, 1 << 21, (B, 3000),
                                          dtype=np.uint32))
    seeds = jnp.asarray([3, 11, 3, 250], jnp.uint32)   # repeats + distinct
    nb = bloom.num_blocks_for(n, 0.01)
    words = ops.build_filter_batched(keys, valid, nb, seeds, interpret=True)
    hits = ops.probe_filter_batched(words, probe_keys, seeds, interpret=True)
    for b in range(B):
        s = int(seeds[b])
        ref_f = bloom.build(keys[b], valid[b], nb, s)
        np.testing.assert_array_equal(np.asarray(words[b]),
                                      np.asarray(ref_f.words))
        one = ops.probe_filter(words[b], probe_keys[b], s, interpret=True)
        np.testing.assert_array_equal(np.asarray(hits[b]), np.asarray(one))
        np.testing.assert_array_equal(
            np.asarray(hits[b]),
            np.asarray(bloom.contains(ref_f, probe_keys[b])))


def test_batched_edge_sample_mixed_seeds_bit_exact():
    """The stacked sampler grid: every slot must match its own single-slot
    kernel call AND the jnp oracle, under per-slot seeds."""
    rng = np.random.default_rng(5)
    B, n, S, b_max = 3, 2048, 256, 128
    seeds = [7, 7, 901]
    slots = []
    for b in range(B):
        r1 = sort_by_key(relation(
            rng.integers(0, S // 2, n).astype(np.uint32),
            rng.normal(3, 1, n).astype(np.float32)))
        r2 = sort_by_key(relation(
            rng.integers(S // 4, S, n).astype(np.uint32),
            rng.normal(1, 2, n).astype(np.float32)))
        strata = build_strata([r1, r2], S)
        slots.append((r1, r2, strata, jnp.ceil(0.3 * strata.population)))
    def stack(xs):
        return jnp.stack(list(xs))
    stats = ops.sample_stats_batched(
        stack(s[0].values for s in slots), stack(s[1].values for s in slots),
        stack(s[2].keys for s in slots), stack(s[2].starts for s in slots),
        stack(s[2].counts for s in slots),
        stack(s[2].joinable for s in slots),
        stack(s[2].population for s in slots), stack(s[3] for s in slots),
        jnp.asarray(seeds, jnp.uint32), b_max, "sum", interpret=True)
    for b, (r1, r2, strata, b_i) in enumerate(slots):
        one = ops.sample_stats([r1, r2], strata, b_i, b_max, seeds[b],
                               interpret=True)
        want = ref.edge_sample_ref(
            r1.values, r2.values, strata.keys,
            strata.starts[0], strata.counts[0],
            strata.starts[1], strata.counts[1],
            strata.joinable, b_i.astype(jnp.float32), b_max, seeds[b])
        for got in (
            (stats.n_sampled[b], stats.sum_f[b], stats.sum_f2[b]),
            (one.n_sampled, one.sum_f, one.sum_f2),
        ):
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_seeds_are_runtime_operands_no_recompiles():
    """The static-seed recompile bug, fixed: a 16-seed sweep through every
    wrapper must compile each executable exactly once."""
    rng = np.random.default_rng(8)
    n, S, b_max = 2048, 128, 64
    keys = jnp.asarray(rng.integers(0, 1 << 16, n, dtype=np.uint32))
    valid = jnp.ones(n, bool)
    nb = bloom.num_blocks_for(n, 0.01)
    r1 = sort_by_key(relation(rng.integers(0, 40, n).astype(np.uint32),
                              rng.normal(0, 1, n).astype(np.float32)))
    r2 = sort_by_key(relation(rng.integers(20, 60, n).astype(np.uint32),
                              rng.normal(0, 1, n).astype(np.float32)))
    strata = build_strata([r1, r2], S)
    b_i = jnp.minimum(strata.population, 50.0)
    jitted = (ops.build_filter_batched, ops.probe_filter_batched,
              ops.sample_stats_batched)
    before = tuple(f._cache_size() for f in jitted)
    for seed in range(16):
        f = ops.build_filter(keys, valid, nb, seed, interpret=True)
        ops.probe_filter(f.words, keys, seed, interpret=True)
        ops.sample_stats([r1, r2], strata, b_i, b_max, seed, interpret=True)
    grew = tuple(f._cache_size() - b for f, b in zip(jitted, before))
    assert all(g <= 1 for g in grew), \
        f"seed sweep recompiled: cache growth {grew}"


def test_prepare_stage_kernels_prebuilt_words_match():
    """The kernel prepare stage accepts prebuilt filter words (the serving
    engine's cache contract) and produces exactly the build-from-scratch
    result — and both match the jnp prepare_stage."""
    from repro.core.join import prepare_stage, prepare_stage_kernels
    rng = np.random.default_rng(3)
    n = 2048
    r1 = relation(rng.integers(0, 300, n).astype(np.uint32),
                  rng.normal(10, 2, n).astype(np.float32))
    r2 = relation(rng.integers(200, 500, n).astype(np.uint32),
                  rng.normal(5, 1, n).astype(np.float32))
    nb = bloom.num_blocks_for(n, 0.01)
    built = prepare_stage_kernels([r1, r2], nb, 512, 5)
    words = jnp.stack([bloom.build(r.keys, r.valid, nb, 5).words
                       for r in (r1, r2)])
    pre = prepare_stage_kernels([r1, r2], nb, 512, 5, filter_words=words)
    ref_prep = prepare_stage([r1, r2], nb, 512, 5)
    for other in (pre, ref_prep):
        np.testing.assert_array_equal(np.asarray(built.strata.keys),
                                      np.asarray(other.strata.keys))
        np.testing.assert_array_equal(np.asarray(built.strata.counts),
                                      np.asarray(other.strata.counts))
        np.testing.assert_array_equal(np.asarray(built.live_counts),
                                      np.asarray(other.live_counts))
        for a, b in zip(built.sorted_rels, other.sorted_rels):
            np.testing.assert_array_equal(np.asarray(a.values),
                                          np.asarray(b.values))


# ---------------------------------------------------------------------------
# Padding unification: wrappers pad, kernels assert, tails never leak.
# ---------------------------------------------------------------------------

def test_raw_kernels_assert_block_multiples():
    """The raw kernels refuse non-multiples — padding is the wrappers' job,
    in exactly one place."""
    with pytest.raises(AssertionError):
        bloom_hashes(jnp.zeros((100,), jnp.uint32), 16, 0)
    with pytest.raises(AssertionError):
        bloom_probe(jnp.zeros((16, 8), jnp.uint32),
                    jnp.zeros((100,), jnp.uint32))
    with pytest.raises(AssertionError):
        edge_sample(jnp.zeros((64,), jnp.float32), jnp.zeros((64,),
                                                            jnp.float32),
                    jnp.zeros((100,), jnp.uint32),
                    jnp.zeros((100,), jnp.int32), jnp.ones((100,), jnp.int32),
                    jnp.zeros((100,), jnp.int32), jnp.ones((100,), jnp.int32),
                    jnp.ones((100,), bool), jnp.ones((100,), jnp.float32),
                    16)


@settings(max_examples=12, deadline=None)
@given(st.integers(min_value=1, max_value=5000),
       st.integers(min_value=0, max_value=2**32 - 1))
def test_padded_tail_never_flips_membership(n, seed32):
    """Hypothesis property: for any key-array length (pow2 or not — the
    wrapper pads the tail) and any seed, kernel probe == jnp membership and
    kernel build == jnp build.  A tail key leaking into the filter or the
    probe output would flip a bit somewhere in this comparison."""
    rng = np.random.default_rng(n * 31 + (seed32 & 0xFFFF))
    seed = int(seed32)
    keys = jnp.asarray(rng.integers(0, 1 << 12, n, dtype=np.uint32))
    valid = jnp.asarray(rng.random(n) > 0.3)
    nb = bloom.num_blocks_for(n, 0.05)
    want = bloom.build(keys, valid, nb, seed)
    got = ops.build_filter(keys, valid, nb, seed, interpret=True)
    np.testing.assert_array_equal(np.asarray(got.words),
                                  np.asarray(want.words))
    m = n + 13 if n % 2 else max(n - 7, 1)   # probe length != build length
    probe_keys = jnp.asarray(rng.integers(0, 1 << 13, m, dtype=np.uint32))
    hits = ops.probe_filter(want.words, probe_keys, seed, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(hits), np.asarray(bloom.contains(want, probe_keys)))
