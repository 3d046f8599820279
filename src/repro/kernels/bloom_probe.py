"""Pallas kernel: join-filter membership probe (the filter hot path).

Every tuple of every input probes the join filter once (§3.1), so this is
the paper's dominant per-tuple cost.  Batched layout (one slot per query of
an engine batch, 2-D grid over ``(batch_slot, key_block)``), two passes:

  1. XLA hashes every key to its filter block (``bloom.block_index``),
     gathers that 8-word block from the slot's packed ``[num_blocks, 8]``
     filter and lays the words out lane-major, ``[B, 8, N/128, 128]``.
     Mosaic lowers no gather over a VMEM-resident table of this size, so
     the gather, and the index it needs, stay in XLA;
  2. this kernel streams ``[block/128, 128]`` key tiles with their 8 word
     planes, computes the lane masks and reduces the 8-lane compare to
     one membership bit per key.

Per-slot seeds are runtime array operands (the ``[B]`` vector in SMEM), so
one compiled executable serves every seed of a mixed-seed batch.  VMEM per
step is a few fixed-size tiles, independent of the filter size and of B.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bloom
from repro.kernels import use_interpret
from repro.kernels.bloom_build import DEFAULT_BLOCK, LANES, key_tiles


def _kernel(seed_ref, keys_ref, words_ref, out_ref):
    seed = seed_ref[pl.program_id(0)]   # this slot's seed (SMEM scalar)
    hit = None
    for w, m in enumerate(bloom.lane_mask_words(keys_ref[...], seed)):
        ok = (words_ref[w] & m) == m
        hit = ok if hit is None else hit & ok
    out_ref[...] = hit


def bloom_probe_batched(words: jnp.ndarray, keys: jnp.ndarray,
                        seeds: jnp.ndarray, block: int = DEFAULT_BLOCK,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Membership mask bool [B, N]: each slot's keys against its own filter.

    ``words`` is the stacked ``[B, num_blocks, 8]`` filter layout; ``seeds``
    is uint32 ``[B]`` (runtime operands — zero recompiles across seeds).
    """
    B, n = keys.shape
    nb, W = words.shape[1], bloom.WORDS_PER_BLOCK
    assert words.shape == (B, nb, W) and seeds.shape == (B,), \
        (words.shape, keys.shape, seeds.shape)
    tiles = key_tiles(keys, block)
    blk = bloom.block_index(keys, nb, seeds[:, None])
    rows = jnp.take_along_axis(words, blk[..., None], axis=1)  # [B, N, 8]
    planes = rows.transpose(0, 2, 1).reshape(B, W, n // LANES, LANES)
    rb = block // LANES
    tile = pl.BlockSpec((None, rb, LANES), lambda b, i: (b, i, 0))
    hits = pl.pallas_call(
        _kernel,
        grid=(B, n // block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile,
                  pl.BlockSpec((None, W, rb, LANES),
                               lambda b, i: (b, 0, i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.bool_),
        interpret=use_interpret(interpret),
    )(seeds, tiles, planes)
    return hits.reshape(B, n)


def bloom_probe(words: jnp.ndarray, keys: jnp.ndarray, seed=0,
                block: int = DEFAULT_BLOCK,
                interpret: bool | None = None) -> jnp.ndarray:
    """Membership mask bool [N] for keys against the packed filter words.

    Single-slot convenience over :func:`bloom_probe_batched` (B = 1).
    """
    seeds = jnp.asarray(seed, jnp.uint32).reshape(1)
    return bloom_probe_batched(words[None], keys[None], seeds, block=block,
                               interpret=interpret)[0]
