"""Pallas kernels: stratified edge sampling (Alg. 2 inner loop).

Per stratum the sampler draws ``b_max`` edges (counter-hash PRNG, same
uint32 math as core.hashing — bit-identical to the oracle), reads both
endpoint values, evaluates f and reduces the masked draws to the stratum's
(n, sum f, sum f^2).  Two kernels with an XLA gather between them:

  1. ``_draw_kernel`` hashes the ``[128, b_max]`` draw tile of 128 strata
     into absolute row indices of each side's sorted value array;
  2. XLA gathers the endpoint values.  The draws may touch anywhere in a
     side's values (2^21 rows at TPC-H SF1), and Mosaic lowers no gather
     over a table that size, so the gather stays in XLA;
  3. ``_reduce_kernel`` evaluates f, masks draws beyond each stratum's
     ``b_i`` (and non-joinable strata) and reduces along the draws.

Batched layout (one slot per query of an engine batch): the grid is 2-D
over ``(strata_block, batch_slot)``, slot innermost.  The ``[B, S, b_max]``
draw tiles stream as ``[128, b_max]`` blocks.  Per-stratum scalars are
lane-dense ``[S/128, B, 128]`` (a column layout would pad every scalar to
128 lanes in HBM); each step transposes its slot's 128-lane row into the
tile's column inside VMEM, and writes its row of the ``[B, 128]`` output
block, which Pallas writes back once per strata block.  Per-slot seeds are
the ``[B]`` SMEM vector: one compiled executable serves every seed.

Two-way joins only (the paper's hot case); n-way falls back to the jnp path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import bounded, counter_hash
from repro.kernels import use_interpret

LANES = 128
S_BLOCK = LANES            # strata per grid step
# scoped-VMEM ceiling the kernels may request (v5e has 128 MiB of VMEM)
VMEM_LIMIT = 96 * 1024 * 1024


def vmem_bytes(b_max: int, slots: int) -> int:
    """VMEM one grid step of either kernel holds, as Mosaic counts it.

    Draw tiles are ``[128, b_max]`` 32-bit with the lanes padded to 128 —
    two streamed operands, double-buffered, plus up to six tile-sized
    temporaries (hash chains, mask, products) — and the per-stratum
    ``[slots, 128]`` rows pad their sublanes to 8; five operand rows,
    double-buffered, plus their transposes.
    """
    lanes = -(-b_max // LANES) * LANES
    tile = S_BLOCK * lanes * 4
    rows = -(-slots // 8) * 8 * LANES * 4
    return (2 * 2 + 6) * tile + (5 * 2) * rows + 8 * LANES * LANES * 4


def _params(b_max: int, slots: int) -> pltpu.CompilerParams:
    need = vmem_bytes(b_max, slots)
    assert need <= VMEM_LIMIT, \
        f"b_max={b_max} draw tiles need {need} bytes of VMEM > {VMEM_LIMIT}"
    return pltpu.CompilerParams(vmem_limit_bytes=max(need, 16 << 20))


def _column(rows_ref) -> jnp.ndarray:
    """This slot's lane-dense 128-stratum row as a ``[128, 1]`` column."""
    row = rows_ref[pl.ds(pl.program_id(1), 1), :]              # [1, 128]
    return jnp.transpose(jnp.broadcast_to(row, (LANES, LANES)))[:, :1]


def _store_row(out_ref, col: jnp.ndarray) -> None:
    """Write a ``[128, 1]`` per-stratum column as this slot's output row."""
    row = jnp.transpose(jnp.broadcast_to(col, (LANES, LANES)))[:1, :]
    out_ref[pl.ds(pl.program_id(1), 1), :] = row


def _draw_kernel(seed_ref, keys_ref, s1_ref, c1_ref, s2_ref, c2_ref,
                 i1_ref, i2_ref, *, b_max: int):
    seed = seed_ref[pl.program_id(1)]   # this slot's seed (SMEM scalar)
    keys = _column(keys_ref)            # int32 bit pattern of the keys
    t = jax.lax.broadcasted_iota(jnp.int32, (1, b_max), 1)
    h1 = counter_hash(seed, keys, t, 0)
    h2 = counter_hash(seed, keys, t, 1)
    i1_ref[...] = _column(s1_ref) + bounded(h1, _column(c1_ref))
    i2_ref[...] = _column(s2_ref) + bounded(h2, _column(c2_ref))


def _reduce_kernel(v1_ref, v2_ref, bi_ref, n_ref, sf_ref, sf2_ref,
                   *, b_max: int, expr: str):
    v1, v2 = v1_ref[...], v2_ref[...]                          # [128, b_max]
    fv = v1 * v2 if expr == "product" else v1 + v2
    t = jax.lax.broadcasted_iota(jnp.int32, (1, b_max), 1)
    mask = t.astype(jnp.float32) < _column(bi_ref)
    fm = jnp.where(mask, fv, 0.0)
    _store_row(n_ref, jnp.sum(mask.astype(jnp.float32), axis=1,
                              keepdims=True))
    _store_row(sf_ref, jnp.sum(fm, axis=1, keepdims=True))
    _store_row(sf2_ref, jnp.sum(fm * fm, axis=1, keepdims=True))


def edge_sample_batched(values1: jnp.ndarray, values2: jnp.ndarray,
                        keys: jnp.ndarray,
                        start1: jnp.ndarray, count1: jnp.ndarray,
                        start2: jnp.ndarray, count2: jnp.ndarray,
                        joinable: jnp.ndarray, b_i: jnp.ndarray,
                        seeds: jnp.ndarray, b_max: int, expr: str = "sum",
                        interpret: bool | None = None):
    """Per-slot per-stratum (n_sampled, sum_f, sum_f2), each float32 [B, S].

    Values are ``[B, n_side]``; per-stratum operands ``[B, S]`` with
    ``S % S_BLOCK == 0`` (wrapper pads); ``seeds`` uint32 ``[B]``.
    """
    B, S = keys.shape
    assert S % S_BLOCK == 0, f"pad strata to a multiple of {S_BLOCK}"
    assert seeds.shape == (B,), (seeds.shape, B)
    for v in (values1, values2):
        assert v.shape[0] == B, (v.shape, B)
    params = _params(b_max, B)
    nblk = S // S_BLOCK

    def rows(x):        # [B, S] -> lane-dense [S/128, B, 128]
        return x.reshape(B, nblk, LANES).transpose(1, 0, 2)

    row = pl.BlockSpec((None, B, LANES), lambda s, b: (s, 0, 0))
    tile = pl.BlockSpec((None, S_BLOCK, b_max), lambda s, b: (b, s, 0))
    draws = jax.ShapeDtypeStruct((B, S, b_max), jnp.int32)
    i1, i2 = pl.pallas_call(
        functools.partial(_draw_kernel, b_max=b_max),
        grid=(nblk, B),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [row] * 5,
        out_specs=[tile, tile],
        out_shape=[draws, draws],
        compiler_params=params,
        interpret=use_interpret(interpret),
    )(seeds, rows(jax.lax.bitcast_convert_type(keys, jnp.int32)),
      rows(start1), rows(count1), rows(start2), rows(count2))

    def gather(values, idx):            # XLA: [B, n] by [B, S, b_max]
        # one plain gather per slot: XLA:TPU compiles the batched form of
        # this gather for minutes at SF1 sizes, the per-slot form in seconds
        return jnp.stack([values[b][idx[b]] for b in range(B)])

    out = jax.ShapeDtypeStruct((nblk, B, LANES), jnp.float32)
    stats = pl.pallas_call(
        functools.partial(_reduce_kernel, b_max=b_max, expr=expr),
        grid=(nblk, B),
        in_specs=[tile, tile, row],
        out_specs=[row, row, row],
        out_shape=[out, out, out],
        compiler_params=params,
        interpret=use_interpret(interpret),
    )(gather(values1, i1), gather(values2, i2),
      rows(jnp.where(joinable, b_i, 0.0)))
    return tuple(x.transpose(1, 0, 2).reshape(B, S) for x in stats)


def edge_sample(values1: jnp.ndarray, values2: jnp.ndarray,
                keys: jnp.ndarray,
                start1: jnp.ndarray, count1: jnp.ndarray,
                start2: jnp.ndarray, count2: jnp.ndarray,
                joinable: jnp.ndarray, b_i: jnp.ndarray,
                b_max: int, seed=0, expr: str = "sum",
                interpret: bool | None = None):
    """Per-stratum (n_sampled, sum_f, sum_f2), each float32 [S].

    Single-slot convenience over :func:`edge_sample_batched` (B = 1).
    """
    seeds = jnp.asarray(seed, jnp.uint32).reshape(1)
    n, sf, sf2 = edge_sample_batched(
        values1[None], values2[None], keys[None], start1[None], count1[None],
        start2[None], count2[None], joinable[None], b_i[None], seeds,
        b_max, expr, interpret=interpret)
    return n[0], sf[0], sf2[0]
