"""Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §7):

  bloom_build  — filter hash computation (scatter-OR commit in the wrapper)
  bloom_probe  — join-filter membership probe (per-tuple hot path)
  edge_sample  — Algorithm-2 sampler (draw kernel -> XLA gather -> reduce)

Every kernel is BATCHED: a leading slot dimension (one slot per query of a
serving-engine batch) with a 2-D grid over the slot and a key or strata
block, lane-dense ``[.., 128]`` tiles, and per-slot seeds as a runtime
``[B]`` SMEM operand — one compiled executable per shape class, zero
recompiles across seeds.  The single-query entry points are the B = 1
specialization of the same kernels.

``ops`` holds the jit'd wrappers (and ALL padding); ``ref`` the pure-jnp
oracles.  On a TPU backend the kernels compile to Mosaic; elsewhere (the
CPU test suite) they run in Pallas interpret mode.
"""

import jax


def use_interpret(interpret: bool | None = None) -> bool:
    """Pallas interpret mode off the TPU; Mosaic-compiled kernels on it.

    An explicit ``interpret`` wins (the tests compile for a described TPU
    from a CPU process with ``False``); ``None`` follows the backend.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
