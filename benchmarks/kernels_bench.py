"""Kernel-path microbenchmarks: join-stage wall times on this host and the
HBM-traffic model of the two samplers (the jnp path materializes six
[S, b_max] grids; the kernel path writes and reads four: two draw-index
grids and the two gathered value grids).  Each kernel row names the mode it
ran in: ``mosaic`` on a TPU, ``interpret`` anywhere else."""

from __future__ import annotations

import numpy as np

from benchmarks.common import row, scaled, timed
from repro.core import bloom
from repro.core.relation import relation, sort_by_key
from repro.core.sampling import build_strata, sample_edges
from repro.kernels import ops

N = scaled(1 << 15, 1 << 12)
S, B_MAX = scaled(1024, 512), scaled(512, 128)


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    r1 = sort_by_key(relation(rng.integers(0, S // 2, N).astype(np.uint32),
                              rng.normal(3, 1, N).astype(np.float32)))
    r2 = sort_by_key(relation(rng.integers(0, S // 2, N).astype(np.uint32),
                              rng.normal(1, 2, N).astype(np.float32)))
    nb = bloom.num_blocks_for(N, 0.01)
    t_build, f = timed(lambda: bloom.build(r1.keys, r1.valid, nb, 0))
    t_probe, _ = timed(lambda: bloom.contains(f, r2.keys))
    strata = build_strata([r1, r2], S)
    import jax.numpy as jnp
    b_i = jnp.ceil(0.2 * strata.population)
    t_jnp, _ = timed(lambda: sample_edges([r1, r2], strata, b_i, B_MAX, 1))
    interpret = ops.use_interpret()
    t_kern, _ = timed(lambda: ops.sample_stats([r1, r2], strata, b_i,
                                               B_MAX, 1, interpret=interpret))
    mode = "interpret" if interpret else "mosaic"
    # HBM-traffic model (f32): jnp path materializes 2 idx + 2 val + f + f^2;
    # the kernel path 2 idx + 2 gathered val, plus the stats it writes
    grid_bytes = S * B_MAX * 4 * 6
    kernel_bytes = S * B_MAX * 4 * 4 + S * 4 * 3
    return [
        row("kernels", stage="bloom_build", seconds=round(t_build, 4),
            n=N),
        row("kernels", stage="bloom_probe", seconds=round(t_probe, 4),
            n=N),
        row("kernels", stage="edge_sample_jnp", seconds=round(t_jnp, 4),
            grid_hbm_mb=round(grid_bytes / 1e6, 1)),
        row("kernels", stage=f"edge_sample_kernel({mode})",
            seconds=round(t_kern, 4),
            kernel_hbm_mb=round(kernel_bytes / 1e6, 1),
            traffic_reduction_x=round(grid_bytes / kernel_bytes, 1)),
    ]
