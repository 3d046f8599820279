"""Integer hashing shared by the Bloom sketch, the sampler, and the Pallas
kernels.

Everything here is uint32 arithmetic (wrap-around multiply / xor / shift) so
the pure-jnp reference paths and the Pallas kernel paths produce bit-identical
results, which the kernel tests assert.

The two primitives are the murmur3 finalizer (``fmix32``) for key hashing and
a counter-based stateless PRNG (``counter_hash``) used for sampling-during-join
draws: ``draw = fmix32(seed ^ fmix32(stratum ^ fmix32(counter)))``.  Stateless
draws are what make the sampler deterministic, replayable after preemption and
coordination-free across devices (see DESIGN.md §2).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Parquet/Impala split-block Bloom filter salts (8 odd constants, one per
# 32-bit lane of the 256-bit block).
SALT = (
    0x47B6137B,
    0x44974D91,
    0x8824AD5B,
    0xA2B7289D,
    0x705495C7,
    0x2DF1424B,
    0x9EFC4947,
    0x5C6BFB31,
)

GOLDEN = 0x9E3779B1  # 2^32 / phi, odd — used for cheap secondary mixing.

# NB: scalar literals are np.uint32, NOT jnp.uint32 — numpy scalars fold into
# the jaxpr as literals, while jnp scalars become captured device constants,
# which Pallas kernels reject ("captures constants ... pass them as inputs").
_U = np.uint32


def u32(x):
    """Cast to uint32 (wrapping); Python ints become numpy scalar literals."""
    if isinstance(x, (int, np.integer)):
        return np.uint32(x & 0xFFFFFFFF)
    return jnp.asarray(x).astype(jnp.uint32)


def fmix32(h: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 32-bit finalizer — a full-avalanche bijection on uint32."""
    if isinstance(h, (int, np.integer)):  # pure-host path (e.g. seed mixing)
        x = int(h) & 0xFFFFFFFF
        x ^= x >> 16
        x = (x * 0x85EBCA6B) & 0xFFFFFFFF
        x ^= x >> 13
        x = (x * 0xC2B2AE35) & 0xFFFFFFFF
        x ^= x >> 16
        return np.uint32(x)
    h = u32(h)
    h = h ^ (h >> _U(16))
    h = h * _U(0x85EBCA6B)
    h = h ^ (h >> _U(13))
    h = h * _U(0xC2B2AE35)
    h = h ^ (h >> _U(16))
    return h


def hash2(key: jnp.ndarray, seed: int | jnp.ndarray) -> jnp.ndarray:
    """Seeded hash: fmix32(key ^ fmix32(seed * GOLDEN))."""
    if isinstance(seed, (int, np.integer)):
        s = fmix32((int(seed) * GOLDEN) & 0xFFFFFFFF)
    else:
        s = fmix32(u32(seed) * _U(GOLDEN))
    return fmix32(u32(key) ^ s)


def counter_hash(seed, stratum, counter, lane) -> jnp.ndarray:
    """Stateless PRNG draw for (stratum, counter, lane) under ``seed``.

    ``lane`` distinguishes the relation side of the bipartite edge draw
    (0 = left endpoint, 1 = right endpoint, ... for multi-way joins).
    All arguments broadcast.
    """
    h = fmix32(u32(counter) * _U(GOLDEN) + u32(lane))
    s = u32(stratum)
    if isinstance(s, np.uint32):  # host-scalar path: avoid np overflow warns
        s = np.uint32((int(s) * 0x85EBCA6B) & 0xFFFFFFFF)
    else:
        s = s * _U(0x85EBCA6B)
    h = fmix32(h ^ s)
    return fmix32(h ^ u32(seed))


def bounded(h: jnp.ndarray, bound: jnp.ndarray) -> jnp.ndarray:
    """Map a uint32 hash into [0, bound) (bound >= 0, int32; 0 acts as 1).

    Plain modulo; the bias is O(bound / 2^32), negligible for the stratum
    sizes we draw from (documented in DESIGN.md).  The clamp runs in the
    signed dtype before the cast: Mosaic has no unsigned max.
    """
    b = u32(jnp.maximum(bound, 1))
    return (h % b).astype(jnp.int32)
