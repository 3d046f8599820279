"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests refuse what the chip's
compiler would refuse — Mosaic lowering errors, VMEM overruns, programs
larger than the 16 GB HBM of one v5e chip — at no chip time.  Shapes are
the chip smoke's TPC-H SF1 customer⋈orders class: ORDERS bucketed to 2^21
rows, CUSTOMER to 2^18, ``max_strata = 2^18``, ``b_max = 512``, 4 slots.

The topology is described inside a module-scoped fixture (never at
import): only one process may load the TPU library, and test workers that
import this file must all collect the same tests.  The persistent compile
cache is off around the compiles — an entry compiled for a described chip
cannot be read back without one.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import bloom
from repro.core.distributed import make_serve_prepare, planned_bucket_cap
from repro.core.relation import Relation
from repro.core.sampling import Strata
from repro.kernels import ops
from repro.runtime.join_serve import (_make_exact, _make_prepare,
                                      _make_prepare_kernels, _make_sample)

ORDERS, CUSTOMERS = 1 << 21, 1 << 18
MAX_STRATA, B_MAX, SLOTS = 1 << 18, 512, 4
NUM_BLOCKS = bloom.num_blocks_for(ORDERS, 0.01)
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.memory_analysis().temp_size_in_bytes


def _kernel_case(kernel: str, B: int, sh):
    u32, i32, f32 = jnp.uint32, jnp.int32, jnp.float32
    seeds = ((B,), u32)
    if kernel == "bloom_build":
        return (lambda k, v, s: ops.build_filter_batched(
                    k, v, NUM_BLOCKS, s, interpret=False),
                _shapes(sh, ((B, ORDERS), u32), ((B, ORDERS), bool), seeds))
    if kernel == "bloom_probe":
        return (lambda w, k, s: ops.probe_filter_batched(
                    w, k, s, interpret=False),
                _shapes(sh, ((B, NUM_BLOCKS, 8), u32), ((B, ORDERS), u32),
                        seeds))
    S = MAX_STRATA
    return (lambda v1, v2, k, st, c, j, p, bi, s: ops.sample_stats_batched(
                v1, v2, k, st, c, j, p, bi, s, B_MAX, "sum",
                interpret=False),
            _shapes(sh, ((B, ORDERS), f32), ((B, CUSTOMERS), f32),
                    ((B, S), u32), ((B, 2, S), i32), ((B, 2, S), i32),
                    ((B, S), bool), ((B, S), f32), ((B, S), f32), seeds))


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("kernel", ["bloom_build", "bloom_probe",
                                    "edge_sample"])
def test_kernel_compiles_for_v5e(one_chip, kernel, B):
    """Every Pallas kernel lowers through Mosaic (a ``tpu_custom_call`` in
    the compiled HLO) at SF1 widths, single- and multi-slot, within HBM."""
    fn, args = _kernel_case(kernel, B, one_chip)
    compiled, temp = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert temp < HBM_BYTES, temp


def _slot_rels(sh):
    return [Relation(*_shapes(sh, ((SLOTS, n), jnp.uint32),
                              ((SLOTS, n), jnp.float32), ((SLOTS, n), bool)))
            for n in (ORDERS, CUSTOMERS)]


def _slot_strata(sh):
    S = MAX_STRATA
    return Strata(*_shapes(sh, ((SLOTS, S), jnp.uint32), ((SLOTS, S), bool),
                           ((SLOTS, 2, S), jnp.int32),
                           ((SLOTS, 2, S), jnp.int32), ((SLOTS,), jnp.int32)))


@pytest.mark.parametrize("stage", ["prepare", "sample"])
def test_jnp_stage_fits_one_chip(one_chip, stage):
    """The server's jnp stage executables at the smoke's SF1 class and
    4-slot batch fit one chip's HBM."""
    sh = one_chip
    rels = _slot_rels(sh)
    seeds = _shapes(sh, ((SLOTS,), jnp.uint32))[0]
    if stage == "prepare":
        words = _shapes(sh, ((SLOTS, 2, NUM_BLOCKS, 8), jnp.uint32))[0]
        _, temp = _compile(_make_prepare(MAX_STRATA), rels, words, seeds)
    else:
        b_i = _shapes(sh, ((SLOTS, MAX_STRATA), jnp.float32))[0]
        _, temp = _compile(_make_sample(B_MAX, "sum", False, 0.95, "sum"),
                           rels, _slot_strata(sh), b_i, seeds)
    assert temp < HBM_BYTES, temp


def test_exact_stage_has_no_search_loop(one_chip):
    """The served exact stage at the SF1 class and 4-slot batch fits one
    chip and has no ``while`` op: each row's stratum slot is read off the
    strata's segments, not found by a binary search (which the TPU compiler
    emits as a loop)."""
    compiled, temp = _compile(_make_exact("sum", "sum"),
                              _slot_rels(one_chip), _slot_strata(one_chip))
    assert not re.search(r"\)\s+while\(", compiled.as_text())
    assert temp < HBM_BYTES, temp


def test_prepare_stage_has_no_search_loop(one_chip):
    """The served kernel prepare at the SF1 class and 4-slot batch fits one
    chip and its strata build has no ``while`` op: each stratum's segment
    is read off the lead's key runs and found in the other side by one
    merge, not by binary searches (which the TPU compiler emits as
    loops)."""
    sh = one_chip
    compiled, temp = _compile(
        _make_prepare_kernels(MAX_STRATA), _slot_rels(sh),
        *_shapes(sh, ((SLOTS, 2, NUM_BLOCKS, 8), jnp.uint32),
                 ((SLOTS,), jnp.uint32)))
    assert not re.search(r"\)\s+while\(.*op_name=\"[^\"]*strata",
                         compiled.as_text())
    assert temp < HBM_BYTES, temp


@pytest.mark.parametrize("merge", ["gather", "psum"])
def test_mesh_prepare_compiles_on_four_chips(topo, merge):
    """The distributed prepare over a 4-chip mesh compiles in both merges,
    with the key shuffle (all-to-all) and the filter / strata exchange
    (all-gather) in the program."""
    mesh = Mesh(topo.devices[:4], ("data",))
    rows = NamedSharding(mesh, P(None, "data"))
    rep = NamedSharding(mesh, P())
    local = ORDERS // 4
    cap = local if merge == "gather" else min(
        1 << (planned_bucket_cap(local, 4, 1.0) - 1).bit_length(), local)
    prepare = make_serve_prepare(mesh, ("data",), n_rels=2,
                                 num_blocks=NUM_BLOCKS,
                                 max_strata=MAX_STRATA, bucket_cap=cap,
                                 merge=merge)
    compiled = prepare.lower(
        _slot_rels(rows),
        *_shapes(rep, ((SLOTS, 2, NUM_BLOCKS, 8), jnp.uint32),
                 ((SLOTS,), jnp.uint32))).compile()
    hlo = compiled.as_text()
    assert "all-to-all" in hlo and "all-gather" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_BYTES
