"""Static-shape relations.

A :class:`Relation` is the TPU-native stand-in for an RDD of key/value pairs:
dense ``keys``/``values`` arrays plus a ``valid`` mask (JAX needs static
shapes, so "fewer rows" is expressed by masking, and every pipeline stage is a
dense pass — the same constraint the paper faces on HDFS, where random access
is off the table).

Values are a single float column; the aggregation queries the paper targets
(SUM / COUNT / AVG / STDEV over an expression of the joined values, §2) only
need one numeric column per side.  Multi-column payloads ride along as extra
Relations with the same keys.
"""

from __future__ import annotations

import hashlib
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Relation(NamedTuple):
    """A (possibly sharded) key/value relation with a validity mask."""

    keys: jnp.ndarray    # uint32 [N]
    values: jnp.ndarray  # float32 [N]
    valid: jnp.ndarray   # bool    [N]

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    def count(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int32))

    def masked_keys(self, fill: int = 0xFFFFFFFF) -> jnp.ndarray:
        """Keys with invalid slots replaced by ``fill`` (sorts to the end)."""
        return jnp.where(self.valid, self.keys, jnp.uint32(fill))


def relation(keys, values=None, valid=None) -> Relation:
    """Build a Relation from array-likes, filling defaults."""
    keys = jnp.asarray(keys, dtype=jnp.uint32)
    if values is None:
        values = jnp.zeros(keys.shape, jnp.float32)
    values = jnp.asarray(values, dtype=jnp.float32)
    if valid is None:
        valid = jnp.ones(keys.shape, bool)
    valid = jnp.asarray(valid, dtype=bool)
    assert keys.shape == values.shape == valid.shape and keys.ndim == 1
    return Relation(keys, values, valid)


def pad_to(rel: Relation, capacity: int) -> Relation:
    """Pad a relation with invalid rows up to ``capacity``."""
    n = rel.capacity
    if n == capacity:
        return rel
    assert n < capacity, f"cannot shrink relation {n} -> {capacity}"
    pad = capacity - n
    return Relation(
        jnp.concatenate([rel.keys, jnp.zeros((pad,), jnp.uint32)]),
        jnp.concatenate([rel.values, jnp.zeros((pad,), jnp.float32)]),
        jnp.concatenate([rel.valid, jnp.zeros((pad,), bool)]),
    )


def pad_shards(rel: Relation, num_shards: int, capacity: int) -> Relation:
    """Pad a relation to ``capacity`` rows with the padding spread evenly
    over ``num_shards`` equal row blocks.

    Block ``d`` holds the next ``N // num_shards`` rows of ``rel`` (one more
    for the first ``N % num_shards`` blocks), then invalid rows.  A mesh
    shards rows in contiguous blocks, so every device gets an equal share of
    the real rows, in order; :func:`pad_to` would put all the padding, and
    none of the rows, on the last devices.
    """
    n = rel.capacity
    if num_shards == 1 or n == capacity:
        return pad_to(rel, capacity)
    per, rem = divmod(capacity, num_shards)
    assert rem == 0 and n <= capacity, (n, num_shards, capacity)
    base, extra = divmod(n, num_shards)
    sizes = base + (np.arange(num_shards) < extra)
    starts = np.cumsum(sizes) - sizes
    shard = np.repeat(np.arange(num_shards), sizes)
    dest = shard * per + np.arange(n) - starts[shard]
    return Relation(*(jnp.zeros(capacity, x.dtype).at[dest].set(x)
                      for x in rel))


def bucket_capacity(n: int, minimum: int = 1) -> int:
    """Round a row count up to the next power of two (shape-class bucketing).

    Serving batches queries whose relations share a capacity bucket, so the
    compiled executable count is logarithmic in the capacity range rather
    than linear in the number of distinct input sizes.  ``minimum`` floors
    the bucket (a mesh-sharded relation needs capacity divisible by the
    device count; any power of two >= k is).
    """
    return max(1 << max(int(n) - 1, 0).bit_length(), int(minimum))


def bucket_to_pow2(rel: Relation, minimum: int = 1) -> Relation:
    """Pad a relation with invalid rows up to its power-of-two bucket."""
    return pad_to(rel, bucket_capacity(rel.capacity, minimum))


def fingerprint(rel: Relation) -> str:
    """Content id of a relation's key set (keys + validity mask).

    Keyed on exactly what a Bloom filter build consumes, so two relations
    with the same keys/validity share cached filter words regardless of
    their value columns (the JoinServer's per-dataset filter cache).
    """
    h = hashlib.sha1()
    h.update(np.asarray(jax.device_get(rel.keys)).tobytes())
    h.update(np.packbits(np.asarray(jax.device_get(rel.valid))).tobytes())
    return h.hexdigest()


def shard_to_mesh(rel: Relation, mesh, axes: Sequence[str]) -> Relation:
    """Place a relation's rows sharded over ``axes`` of ``mesh``."""
    from jax.sharding import NamedSharding, PartitionSpec
    sh = NamedSharding(mesh, PartitionSpec(tuple(axes)))
    return Relation(*(jax.device_put(x, sh) for x in rel))


def place_rows(rel: Relation, capacity: int, mesh=None,
               axes: Sequence[str] = ()) -> Relation:
    """Admit a relation: pad it to ``capacity`` rows and, on a mesh, shard
    its rows over ``axes`` with every device's row block padded
    (:func:`pad_shards`), so each device holds its share of the real rows."""
    if mesh is None:
        return pad_to(rel, capacity)
    k = int(np.prod([mesh.shape[a] for a in axes]))
    return shard_to_mesh(pad_shards(rel, k, capacity), mesh, axes)


def sort_by_key(rel: Relation) -> Relation:
    """Sort valid rows by key; invalid rows go last (stable)."""
    order = jnp.argsort(rel.masked_keys())
    return Relation(rel.keys[order], rel.values[order], rel.valid[order])


def concatenate(rels: list[Relation]) -> Relation:
    return Relation(
        jnp.concatenate([r.keys for r in rels]),
        jnp.concatenate([r.values for r in rels]),
        jnp.concatenate([r.valid for r in rels]),
    )


def shard_rows(rel: Relation, num_shards: int) -> Relation:
    """Reshape [N] -> [num_shards, N/num_shards] for shard_map feeding."""
    assert rel.capacity % num_shards == 0
    f = lambda x: x.reshape(num_shards, -1)
    return Relation(f(rel.keys), f(rel.values), f(rel.valid))


def to_numpy(rel: Relation):
    """(keys, values) of the valid rows as host numpy arrays (test helper)."""
    k = np.asarray(jax.device_get(rel.keys))
    v = np.asarray(jax.device_get(rel.values))
    m = np.asarray(jax.device_get(rel.valid))
    return k[m], v[m]
