"""Pallas kernel: Bloom-filter hash computation (build side, Alg. 1 map).

Batched layout: every array carries a leading SLOT dimension (one slot per
query of an engine batch) and the grid is 2-D over ``(batch_slot,
key_block)``.  Keys are laid out lane-dense as ``[B, N/128, 128]``; each
step loads a ``[block/128, 128]`` tile of one slot's keys into VMEM and
emits the block index and the 8 lane bit masks of every key — pure VPU
integer math (murmur3 finalizer + multiply-shift lane hashes), no memory
traffic beyond the streaming key tiles.  The masks come out lane-major
(``[B, 8, N/128, 128]``, one dense plane per filter word) and the wrapper
transposes them to the ``[B, N, 8]`` layout the scatter commit takes.

Seeds are RUNTIME OPERANDS, not static kernel parameters: the whole ``[B]``
seed vector sits in SMEM and each step reads its slot's scalar, so one
compiled executable serves every seed (the serving engine's zero-recompile
contract across mixed-seed batches).

The scatter-OR that folds these pairs into the packed filter runs in the jit
wrapper (XLA scatter): TPU Pallas has no scatter atomics, so committing the
bits from inside the kernel would serialize the grid.  This is the documented
GPU->TPU semantic change (DESIGN.md §2): the paper's per-worker loop becomes
hash-kernel + one XLA scatter pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bloom
from repro.kernels import use_interpret

LANES = 128
DEFAULT_BLOCK = 2048          # keys per grid step: a [16, 128] tile


def _kernel(seed_ref, keys_ref, blk_ref, masks_ref, *, num_blocks: int):
    seed = seed_ref[pl.program_id(0)]   # this slot's seed (SMEM scalar)
    keys = keys_ref[...]                # [block/128, 128]
    blk_ref[...] = bloom.block_index(keys, num_blocks, seed)
    for w, m in enumerate(bloom.lane_mask_words(keys, seed)):
        masks_ref[w] = m


def key_tiles(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """``[B, N]`` -> lane-dense ``[B, N/128, 128]`` (a free reshape)."""
    B, n = x.shape
    assert n % block == 0 and block % (8 * LANES) == 0, \
        f"pad keys to a multiple of {block} (got {n})"
    return x.reshape(B, n // LANES, LANES)


def bloom_hashes_batched(keys: jnp.ndarray, seeds: jnp.ndarray,
                         num_blocks: int, block: int = DEFAULT_BLOCK,
                         interpret: bool | None = None):
    """(block_index int32 [B, N], lane_masks uint32 [B, N, 8]) per slot.

    ``keys`` is ``[B, N]`` with ``N % block == 0`` (wrappers pad);
    ``seeds`` is uint32 ``[B]`` — a runtime array operand, one per slot.
    """
    B, n = keys.shape
    assert seeds.shape == (B,), (seeds.shape, B)
    W, rows, rb = bloom.WORDS_PER_BLOCK, n // LANES, block // LANES
    tile = pl.BlockSpec((None, rb, LANES), lambda b, i: (b, i, 0))
    blk, masks = pl.pallas_call(
        functools.partial(_kernel, num_blocks=num_blocks),
        grid=(B, n // block),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), tile],
        out_specs=[tile, pl.BlockSpec((None, W, rb, LANES),
                                      lambda b, i: (b, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, rows, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((B, W, rows, LANES), jnp.uint32)],
        interpret=use_interpret(interpret),
        name="bloom_build_hashes",
    )(seeds, key_tiles(keys, block))
    return blk.reshape(B, n), masks.reshape(B, W, n).transpose(0, 2, 1)


def bloom_hashes(keys: jnp.ndarray, num_blocks: int, seed=0,
                 block: int = DEFAULT_BLOCK, interpret: bool | None = None):
    """(block_index int32 [N], lane_masks uint32 [N, 8]); N % block == 0.

    Single-slot convenience over :func:`bloom_hashes_batched` (B = 1) —
    the batched kernel IS the implementation, so the two can never drift.
    """
    seeds = jnp.asarray(seed, jnp.uint32).reshape(1)
    blk, masks = bloom_hashes_batched(keys[None], seeds, num_blocks,
                                      block=block, interpret=interpret)
    return blk[0], masks[0]
