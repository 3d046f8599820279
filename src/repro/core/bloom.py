"""Split-block Bloom filters (the paper's sketch, TPU-adapted).

The paper uses a flat bit-vector Bloom filter (§3.1, Algorithm 1).  On TPU we
use the *split-block* variant (Parquet/Impala): a key selects one 256-bit
block (8 x uint32 lanes) and sets exactly one bit in each lane, chosen by
eight per-lane salted hashes.  Build and probe are then gathers plus lane-wise
bitwise ops on aligned 8-word vectors — VPU-friendly, one block touch per key
instead of h random bit probes (DESIGN.md §2).

Filter algebra is unchanged from the paper:
  * partition filters merge with OR   (Algorithm 1, reduce phase)
  * dataset filters merge with AND    (Algorithm 1, join filter)
and those are plain ``bitwise_or`` / ``bitwise_and`` on the packed words, so a
distributed merge is an all-gather + fold (or any reduction tree XLA picks).

Sizing uses the paper's Eq. 27, |BF| = -N ln p / (ln 2)^2 bits, rounded up to
a power-of-two number of blocks; the split-block layout costs a small constant
in false-positive rate versus the optimal flat filter, which the property
tests bound empirically.

Appendix-B variants (counting / invertible / scalable) are provided as a
functional counting filter plus size models for the Fig-15 benchmark.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.hashing import SALT, fmix32, hash2, u32

WORDS_PER_BLOCK = 8
BITS_PER_BLOCK = 32 * WORDS_PER_BLOCK


class BloomFilter(NamedTuple):
    """Packed split-block Bloom filter: uint32 words [num_blocks, 8]."""

    words: jnp.ndarray
    seed: int = 0

    @property
    def num_blocks(self) -> int:
        return self.words.shape[0]

    @property
    def num_bits(self) -> int:
        return self.num_blocks * BITS_PER_BLOCK

    @property
    def size_bytes(self) -> int:
        return self.num_bits // 8


def num_blocks_for(n_keys: int, fp_rate: float) -> int:
    """Paper Eq. 27 sizing, rounded up to a power-of-two block count."""
    n_keys = max(int(n_keys), 1)
    bits = -n_keys * math.log(max(min(fp_rate, 0.5), 1e-12)) / (math.log(2) ** 2)
    blocks = max(1, math.ceil(bits / BITS_PER_BLOCK))
    return 1 << (blocks - 1).bit_length()


def block_index(keys: jnp.ndarray, num_blocks: int, seed) -> jnp.ndarray:
    """Which block each key lands in (num_blocks must be a power of two)."""
    return (hash2(keys, seed) & u32(num_blocks - 1)).astype(jnp.int32)


def lane_mask_words(keys: jnp.ndarray, seed) -> list[jnp.ndarray]:
    """The 8 one-bit-per-lane masks of each key, one keys-shaped uint32
    array per filter word (the form the Pallas kernels consume).

    Scalar numpy literals per lane (not a stacked device array) so this
    traces cleanly inside Pallas kernels (see core.hashing note).
    """
    h = fmix32(hash2(keys, seed) * u32(0x85EBCA6B) + u32(1))
    lanes = []
    for s in SALT:
        # bit position in lane = top 5 bits of (h * salt)
        bits = (h * u32(s)) >> u32(27)
        lanes.append((u32(1) << bits).astype(jnp.uint32))
    return lanes


def lane_masks(keys: jnp.ndarray, seed) -> jnp.ndarray:
    """[..., 8] uint32 — the one-bit-per-lane masks for each key."""
    return jnp.stack(lane_mask_words(keys, seed), axis=-1)


def empty(num_blocks: int, seed: int = 0) -> BloomFilter:
    return BloomFilter(jnp.zeros((num_blocks, WORDS_PER_BLOCK), jnp.uint32), seed)


def scatter_or(blk: jnp.ndarray, masks: jnp.ndarray, valid: jnp.ndarray,
               num_blocks: int, seed: int = 0) -> BloomFilter:
    """Scatter-OR (block, mask) pairs into a packed filter.

    TPU Pallas has no scatter atomics, so the scatter-OR is expressed as an
    unpacked scatter-max over bits ([num_blocks, 8, 32] uint8) and packed once
    at the end; the Pallas build kernel computes the (block, mask) pairs and
    this same scatter runs in its jit wrapper (see kernels/bloom_build).
    """
    blk = jnp.where(valid, blk, num_blocks)  # overflow row is dropped
    bits = _unpack(masks)  # [N, 8, 32] uint8
    grid = jnp.zeros((num_blocks + 1, WORDS_PER_BLOCK, 32), jnp.uint8)
    grid = grid.at[blk].max(bits)
    return BloomFilter(_pack(grid[:num_blocks]), seed)


def build(keys: jnp.ndarray, valid: jnp.ndarray, num_blocks: int,
          seed: int = 0) -> BloomFilter:
    """Build a filter over the valid keys (pure-jnp reference path)."""
    blk = block_index(keys, num_blocks, seed)
    masks = lane_masks(keys, seed)  # [N, 8]
    return scatter_or(blk, masks, valid, num_blocks, seed)


def contains(f: BloomFilter, keys: jnp.ndarray) -> jnp.ndarray:
    """Membership probe (pure-jnp reference; hot path has a Pallas kernel)."""
    blk = block_index(keys, f.num_blocks, f.seed)
    masks = lane_masks(keys, f.seed)
    gathered = f.words[blk]  # [N, 8]
    return jnp.all((gathered & masks) == masks, axis=-1)


def union(a: BloomFilter, b: BloomFilter) -> BloomFilter:
    """OR-merge (partition filters -> dataset filter)."""
    assert a.seed == b.seed and a.num_blocks == b.num_blocks
    return BloomFilter(a.words | b.words, a.seed)


def intersect(a: BloomFilter, b: BloomFilter) -> BloomFilter:
    """AND-merge (dataset filters -> join filter).

    As in the paper, the AND of Bloom filters is a filter whose set is a
    superset of the intersection of the sets (false positives possible, false
    negatives not).
    """
    assert a.seed == b.seed and a.num_blocks == b.num_blocks
    return BloomFilter(a.words & b.words, a.seed)


def intersect_all(filters: list[BloomFilter]) -> BloomFilter:
    """AND-merge n dataset filters into the join filter (§3.1, Alg. 1).

    Validates that the filters agree before merging: intersecting filters
    with different geometry or hash seeds silently returns garbage (the AND
    of unrelated bit patterns).  Word shapes are static and always checked;
    seeds are compared only when both are concrete Python ints — under
    jit/vmap the seed is a tracer (one seed per batch slot) and equality
    cannot be evaluated at trace time, which is exactly the case where the
    caller passes the *same* seed object to every filter anyway.
    """
    filters = list(filters)
    if not filters:
        raise ValueError("intersect_all: need at least one filter")
    first = filters[0]
    words = first.words
    for i, f in enumerate(filters[1:], start=1):
        if f.words.shape != first.words.shape:
            raise ValueError(
                f"intersect_all: filter {i} words shape {f.words.shape} != "
                f"filter 0 shape {first.words.shape} (num_blocks mismatch)")
        if (isinstance(f.seed, int) and isinstance(first.seed, int)
                and f.seed != first.seed):
            raise ValueError(
                f"intersect_all: filter {i} seed {f.seed} != filter 0 seed "
                f"{first.seed} — filters hash incompatibly")
        words = words & f.words
    return BloomFilter(words, first.seed)


def _unpack(words: jnp.ndarray) -> jnp.ndarray:
    """uint32 [..., W] -> uint8 bits [..., W, 32]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return ((words[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.uint8)


def _pack(bits: jnp.ndarray) -> jnp.ndarray:
    """uint8 bits [..., W, 32] -> uint32 [..., W]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bits.astype(jnp.uint32) << shifts, axis=-1, dtype=jnp.uint32)


def fill_fraction(f: BloomFilter) -> jnp.ndarray:
    """Fraction of set bits (sanity metric; ~0.5 at design load)."""
    return jnp.mean(_unpack(f.words).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Appendix-B variants: size models + a functional counting filter.
# ---------------------------------------------------------------------------

def flat_filter_bits(n_keys: int, fp_rate: float) -> int:
    """Regular Bloom filter size (paper Eq. 27), in bits."""
    n_keys = max(int(n_keys), 1)
    return math.ceil(-n_keys * math.log(fp_rate) / (math.log(2) ** 2))


def counting_filter_bits(n_keys: int, fp_rate: float, counter_bits: int = 4) -> int:
    """Counting BF: a ``counter_bits`` counter per cell instead of one bit."""
    return flat_filter_bits(n_keys, fp_rate) * counter_bits


def invertible_filter_bits(n_keys: int, fp_rate: float,
                           key_bits: int = 32, count_bits: int = 32) -> int:
    """IBF: each cell stores (count, keySum, hashSum) — modeled per [26]."""
    cells = flat_filter_bits(n_keys, fp_rate) // 8  # h≈ln2·bits/n, cells≈1.5n..
    cells = max(cells, int(1.3 * n_keys))
    return cells * (count_bits + key_bits + key_bits)


def scalable_filter_bits(n_keys: int, fp_rate: float, initial: int = 4096,
                         growth: int = 2, tightening: float = 0.9) -> int:
    """SBF [41]: series of filters of growing size / tightening error."""
    total, cap, err, added = 0, initial, fp_rate * (1 - tightening), 0
    while added < n_keys:
        total += flat_filter_bits(cap, err)
        added += cap
        cap *= growth
        err *= tightening
    return total


class CountingFilter(NamedTuple):
    """Functional counting Bloom filter (supports remove), Appendix B-II."""

    counts: jnp.ndarray  # int32 [num_blocks, 8, 32] (unpacked cells)
    seed: int = 0

    @property
    def num_blocks(self) -> int:
        return self.counts.shape[0]


def counting_empty(num_blocks: int, seed: int = 0) -> CountingFilter:
    return CountingFilter(jnp.zeros((num_blocks, WORDS_PER_BLOCK, 32), jnp.int32), seed)


def counting_add(f: CountingFilter, keys, valid, sign: int = 1) -> CountingFilter:
    blk = block_index(keys, f.num_blocks, f.seed)
    bits = _unpack(lane_masks(keys, f.seed)).astype(jnp.int32) * sign
    blk = jnp.where(valid, blk, f.num_blocks)
    grid = jnp.zeros((f.num_blocks + 1,) + f.counts.shape[1:], jnp.int32)
    grid = grid.at[blk].add(bits)
    return CountingFilter(f.counts + grid[: f.num_blocks], f.seed)


def counting_contains(f: CountingFilter, keys) -> jnp.ndarray:
    packed = BloomFilter(_pack((f.counts > 0).astype(jnp.uint8)), f.seed)
    return contains(packed, keys)


def false_positive_rate(num_blocks: int, n_keys: int) -> float:
    """Predicted FPR of the split-block filter at load n_keys.

    Per-lane analysis: each lane of a block holding ``c`` keys has FPR
    1-(1-1/32)^c; block FPR = prod over 8 lanes; averaged over the Poisson
    block-occupancy distribution (numpy, used for sizing sanity checks).
    """
    lam = n_keys / num_blocks
    cs = np.arange(0, max(int(lam * 8), 16) + 1)
    # log-space Poisson pmf (factorials overflow past ~170)
    logpmf = -lam + cs * np.log(max(lam, 1e-12)) \
        - np.array([math.lgamma(int(c) + 1) for c in cs])
    pois = np.exp(logpmf)
    per_lane = 1.0 - (1.0 - 1.0 / 32.0) ** cs
    return float(np.sum(pois * per_lane ** WORDS_PER_BLOCK))


# ---------------------------------------------------------------------------
# Appendix B-III: functional Scalable Bloom Filter with the UNION operation
# (the merge the paper contributed upstream — "SBFs contain a set of regular
# Bloom filters, so union two SBFs by unioning the stages pairwise").
# ---------------------------------------------------------------------------

class ScalableFilter:
    """Host-managed SBF: a list of split-block stages of doubling capacity
    and tightening error; add() spills to a fresh stage when the current one
    reaches its design load.  JAX arrays inside, Python growth control (the
    structure is data-dependent, which is exactly why the static pipeline
    uses fixed-size filters — this variant serves ad-hoc driver-side use)."""

    def __init__(self, initial_capacity: int = 4096, fp_rate: float = 0.01,
                 growth: int = 2, tightening: float = 0.5, seed: int = 0):
        self.growth = growth
        self.tightening = tightening
        self.seed = seed
        self.stages: list[BloomFilter] = []
        self.caps: list[int] = []
        self.errs: list[float] = []
        self.counts: list[int] = []
        self._next_cap = initial_capacity
        self._next_err = fp_rate * (1 - tightening)

    def _push_stage(self) -> None:
        nb = num_blocks_for(self._next_cap, self._next_err)
        self.stages.append(empty(nb, self.seed))
        self.caps.append(self._next_cap)
        self.errs.append(self._next_err)
        self.counts.append(0)
        self._next_cap *= self.growth
        self._next_err *= self.tightening

    def add(self, keys) -> None:
        keys = jnp.asarray(keys, jnp.uint32).reshape(-1)
        while keys.shape[0]:
            if not self.stages or self.counts[-1] >= self.caps[-1]:
                self._push_stage()
            room = self.caps[-1] - self.counts[-1]
            batch, keys = keys[:room], keys[room:]
            add = build(batch, jnp.ones(batch.shape[0], bool),
                        self.stages[-1].num_blocks, self.seed)
            self.stages[-1] = union(self.stages[-1], add)
            self.counts[-1] += int(batch.shape[0])

    def contains(self, keys) -> jnp.ndarray:
        keys = jnp.asarray(keys, jnp.uint32)
        out = jnp.zeros(keys.shape, bool)
        for st in self.stages:
            out = out | contains(st, keys)
        return out

    def merge(self, other: "ScalableFilter") -> "ScalableFilter":
        """Union of two SBFs: pairwise-union stages of equal geometry,
        carry extra stages verbatim (the upstream-PR semantics)."""
        assert self.seed == other.seed
        a, b = self, other
        out = ScalableFilter(seed=self.seed)
        n = max(len(a.stages), len(b.stages))
        for i in range(n):
            if i < len(a.stages) and i < len(b.stages):
                assert a.stages[i].num_blocks == b.stages[i].num_blocks, \
                    "stage geometry mismatch: merge requires same schedule"
                out.stages.append(union(a.stages[i], b.stages[i]))
                out.caps.append(a.caps[i])
                out.errs.append(a.errs[i])
                out.counts.append(a.counts[i] + b.counts[i])
            else:
                src = a if i < len(a.stages) else b
                out.stages.append(src.stages[i])
                out.caps.append(src.caps[i])
                out.errs.append(src.errs[i])
                out.counts.append(src.counts[i])
        if out.caps:
            out._next_cap = out.caps[-1] * out.growth
            out._next_err = out.errs[-1] * out.tightening
        return out
