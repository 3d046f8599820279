"""Cross-mesh parity suite for the distributed JoinServer.

The contract under test: a JoinServer constructed with a mesh of ANY size
produces results bit-identical to (a) the single-device JoinServer and
(b) direct ``distributed_approx_join`` calls, under the same seed — the
shuffle routes every key to one device, received rows arrive in original
row order, per-stratum statistics are computed by the owning device and
merged back into the canonical [S] slot layout, so every float is the same.

Runs in a SUBPROCESS with --xla_force_host_platform_device_count=8 so the
rest of the suite keeps the real single-device backend.  Mesh sizes 1/2/4
use device subsets of the 8 placeholder devices.
"""

import os
import subprocess
import sys

import pytest

from repro.core.budget import QueryBudget
from repro.runtime.join_serve import JoinRequest, ShapeClass, shape_class_of

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.budget import QueryBudget
from repro.core.distributed import distributed_approx_join
from repro.core.relation import relation
from repro.runtime.join_serve import JoinRequest, JoinServer

MS, BM = 1024, 512
rng = np.random.default_rng(0)
n = 1 << 12
r1 = relation(rng.integers(0, 500, n).astype(np.uint32),
              rng.normal(10, 2, n).astype(np.float32))
r2 = relation(rng.integers(400, 900, n).astype(np.uint32),
              rng.normal(5, 1, n).astype(np.float32))


def req(qid, seed, budget=None):
    return JoinRequest(dataset="ds", budget=budget or QueryBudget(error=0.5),
                       query_id=qid, seed=seed, max_strata=MS, b_max=BM)


def surface(q):
    r = q.result
    return (float(r.estimate), float(r.error_bound), float(r.count),
            float(r.dof))


def serve(server):
    qs = [server.submit(req("tA", 5)),                   # pilot round
          server.submit(req("tB", 6)),
          server.submit(req("tC", 7, QueryBudget())),    # exact path
          server.submit(req("tA", 8))]                   # sigma round 2
    server.run()
    return [surface(q) for q in qs], qs


ref_srv = JoinServer(batch_slots=2)
ref_srv.register_dataset("ds", [r1, r2])
ref, ref_qs = serve(ref_srv)

# --- direct distributed_approx_join references (same seeds) ---------------
for d in (1, 2, 4, 8):
    mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
    dist = distributed_approx_join(mesh, [r1, r2], mode="exact",
                                   max_strata=MS, seed=7)
    assert float(dist.estimate) == ref[2][0], (d, "exact estimate")
    assert float(dist.count) == ref[2][2], (d, "exact count")
    samp = distributed_approx_join(mesh, [r1, r2], mode="sample",
                                   sample_fraction=0.1, b_max=BM,
                                   max_strata=MS, seed=5)
    assert (float(samp.estimate), float(samp.error_bound),
            float(samp.count), float(samp.dof)) == ref[0], (d, "sampled")
print("DIRECT-PARITY-OK")

# --- mesh servers: bit-identical results + sigma feedback ------------------
for d in (1, 2, 4, 8):
    mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
    srv = JoinServer(batch_slots=2, mesh=mesh)
    srv.register_dataset("ds", [r1, r2])
    got, qs = serve(srv)
    assert got == ref, (d, got, ref)
    assert srv.sigma.table == ref_srv.sigma.table, d
    # diagnostics surfaces survive the distributed path
    q = qs[0]
    np.testing.assert_array_equal(
        np.asarray(q.result.diagnostics.live_counts),
        np.asarray(ref_qs[0].result.diagnostics.live_counts))
    np.testing.assert_array_equal(np.asarray(q.result.strata.keys),
                                  np.asarray(ref_qs[0].result.strata.keys))
    d8 = srv.diagnostics
    assert d8.per_device_shuffled_bytes.shape == (d,)
    if d > 1:
        assert d8.dist_shuffled_tuple_bytes > 0
        assert all(b > 0 for b in d8.per_device_shuffled_bytes)
print("SERVER-PARITY-OK")

# --- mesh-keyed shape classes: warm then zero recompiles -------------------
mesh = Mesh(np.array(jax.devices()), ("data",))
srv = JoinServer(batch_slots=2, mesh=mesh)
srv.register_dataset("ds", [r1, r2])
for q in range(2):   # warmup covers (fbuild, prepare, sample, exact) x B
    srv.submit(req(f"w{q}", 11))
    srv.submit(req(f"we{q}", 11, QueryBudget()))
srv.run()
warm = srv.diagnostics.snapshot()
assert warm["compiles"] >= 4, warm
for q in range(4):
    srv.submit(req(f"m{q}", 11))
    srv.submit(req(f"me{q}", 11, QueryBudget()))
srv.run()
after = srv.diagnostics.snapshot()
assert after["compiles"] == warm["compiles"], (warm, after)
assert after["cache_hits"] > warm["cache_hits"]
# dataset filter words were built once per relation for seed 11 and reused
assert after["filter_builds"] == warm["filter_builds"]
assert after["filter_cache_hits"] > warm["filter_cache_hits"]
print("CACHE-OK")

# --- kernel route on mesh servers: the single-device Pallas path gathers
# --- sharded rows to the host (metered, zero at mesh 1), results identical
from repro.core.join import approx_join

kref = approx_join([r1, r2], QueryBudget(error=0.5), max_strata=MS,
                   b_max=BM, seed=21, use_kernels=True)
for d in (1, 2, 8):
    mesh = Mesh(np.array(jax.devices()[:d]), ("data",))
    srv = JoinServer(batch_slots=2, mesh=mesh)
    srv.register_dataset("ds", [r1, r2])
    q = srv.submit(JoinRequest(dataset="ds", budget=QueryBudget(error=0.5),
                               query_id="k0", seed=21, max_strata=MS,
                               b_max=BM, use_kernels=True))
    srv.run()
    assert surface(q) == (float(kref.estimate), float(kref.error_bound),
                          float(kref.count), float(kref.dof)), d
    assert srv.diagnostics.kernel_queries == 1, d
    if d == 1:
        assert srv.diagnostics.kernel_gather_bytes == 0.0, d
    else:
        assert srv.diagnostics.kernel_gather_bytes > 0, d
    if d == 2:
        bytes_one = srv.diagnostics.kernel_gather_bytes

# gathers are memoized per distinct array within a step: a 2-slot batch of
# the SAME dataset (shared rows + shared filter words) moves exactly the
# bytes one query does
srv = JoinServer(batch_slots=2,
                 mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
srv.register_dataset("ds", [r1, r2])
for i in (0, 1):
    srv.submit(JoinRequest(dataset="ds", budget=QueryBudget(error=0.5),
                           query_id=f"k{i}", seed=21 + i, filter_seed=21,
                           max_strata=MS, b_max=BM, use_kernels=True))
assert srv.step() == 2
assert srv.diagnostics.kernel_gather_bytes == bytes_one, \
    (srv.diagnostics.kernel_gather_bytes, bytes_one)
print("KERNEL-MESH-OK")
"""


@pytest.mark.slow
def test_distributed_server_parity_1_2_4_8():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("DIRECT-PARITY-OK", "SERVER-PARITY-OK", "CACHE-OK",
                   "KERNEL-MESH-OK"):
        assert marker in out.stdout, (marker, out.stdout[-2000:])


def _mesh1_server(**kw):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.runtime.join_serve import JoinServer
    return JoinServer(mesh=Mesh(np.array(jax.devices()[:1]), ("data",)),
                      **kw)


def test_serve_mode_cache_isolation(rng):
    """psum and exact-parity entries never collide in the executable cache:
    switching modes compiles fresh programs once, then each mode hits its
    own entries — no recompiles of the other mode's executables.  At mesh
    size 1 both modes run the same arithmetic, so results must agree."""
    from conftest import make_pair
    from repro.core.budget import QueryBudget
    from repro.runtime.join_serve import JoinRequest

    r1, r2 = make_pair(rng, n=1 << 11)
    srv = _mesh1_server(batch_slots=2)
    srv.register_dataset("ds", [r1, r2])

    def submit(mode, seed):
        return srv.submit(JoinRequest(
            dataset="ds", budget=QueryBudget(error=0.5), query_id=f"{mode}",
            seed=seed, max_strata=512, b_max=128, serve_mode=mode))

    q_par = submit("exact-parity", 7)
    srv.run()
    c_parity = srv.diagnostics.compiles
    q_psum = submit("psum", 7)
    srv.run()
    c_both = srv.diagnostics.compiles
    assert c_both > c_parity                  # psum compiled its own stages
    assert q_par._class != q_psum._class
    assert q_par._class._replace(
        serve_mode="psum", bucket_cap=q_psum._class.bucket_cap) \
        == q_psum._class                      # the ONLY key difference
    # alternate modes (same batch bucket): zero further compiles either way
    for seed in (8, 9):
        submit("exact-parity", seed)
        srv.run()
        submit("psum", seed)
        srv.run()
    assert srv.diagnostics.compiles == c_both
    assert srv.diagnostics.cache_hits > 0
    # one device: the psum merge degenerates to the canonical arithmetic
    assert float(q_psum.result.estimate) == float(q_par.result.estimate)
    assert float(q_psum.result.error_bound) == float(q_par.result.error_bound)


def test_meshless_server_normalizes_serve_mode(rng):
    """Off-mesh there is one pipeline (the exact one): psum requests fold
    into the exact-parity shape class instead of forking the cache."""
    from conftest import make_pair
    from repro.core.budget import QueryBudget
    from repro.runtime.join_serve import JoinRequest, JoinServer

    r1, r2 = make_pair(rng, n=1 << 10)
    srv = JoinServer(batch_slots=2)
    q = srv.submit(JoinRequest(rels=[r1, r2], budget=QueryBudget(error=0.5),
                               query_id="t", seed=1, max_strata=256,
                               b_max=128, serve_mode="psum"))
    assert q._class.serve_mode == "exact-parity"
    assert q._class.bucket_cap == 0
    with pytest.raises(ValueError):
        srv.submit(JoinRequest(rels=[r1, r2], budget=QueryBudget(),
                               query_id="t", max_strata=256, b_max=128,
                               serve_mode="gossip"))


def test_forced_bucket_overflow_is_counted(rng):
    """An under-provisioned bucket plan must COUNT what it drops — in the
    server totals, per device, and on the per-query result diagnostics —
    and the count estimate shrinks accordingly (never silently)."""
    from conftest import make_pair
    from repro.core.budget import QueryBudget
    from repro.runtime.join_serve import JoinRequest

    r1, r2 = make_pair(rng, n=1 << 11)
    srv = _mesh1_server(batch_slots=1, serve_mode="psum", bucket_cap=64)
    srv.register_dataset("ds", [r1, r2])
    lossless = _mesh1_server(batch_slots=1, serve_mode="psum")
    lossless.register_dataset("ds", [r1, r2])

    def ask(server):
        q = server.submit(JoinRequest(dataset="ds", budget=QueryBudget(),
                                      query_id="t", seed=3, max_strata=2048,
                                      b_max=128))
        server.run()
        return q

    q_tight, q_free = ask(srv), ask(lossless)
    d = srv.diagnostics
    assert d.dist_dropped_tuples > 0
    assert d.per_device_dropped_tuples.sum() == d.dist_dropped_tuples
    assert float(q_tight.result.diagnostics.dist_dropped_tuples) \
        == d.dist_dropped_tuples
    assert lossless.diagnostics.dist_dropped_tuples == 0
    assert float(q_free.result.diagnostics.dist_dropped_tuples) == 0.0
    assert float(q_tight.result.count) < float(q_free.result.count)


def test_shape_class_keys_on_mesh_shape(rng):
    """Same query admitted on different mesh shapes lands in different
    executable-cache classes (no cross-mesh executable collisions)."""
    from conftest import make_pair
    r1, r2 = make_pair(rng, n=1 << 10)
    req = JoinRequest(rels=[r1, r2], budget=QueryBudget(error=0.5),
                      max_strata=512, b_max=128)
    single = shape_class_of(req)
    mesh8 = shape_class_of(req, (("data", 8),))
    mesh2x4 = shape_class_of(req, (("pod", 2), ("data", 4)))
    assert single.mesh == ()
    assert len({single, mesh8, mesh2x4}) == 3
    assert isinstance(single, ShapeClass)
    # everything but the mesh key is identical
    assert single._replace(mesh=(("data", 8),)) == mesh8


@pytest.mark.parametrize("n,k,capacity", [(1500, 4, 2048), (1500, 1, 2048),
                                          (2048, 4, 2048), (5, 4, 8)])
def test_pad_shards_spreads_real_rows_over_every_shard(n, k, capacity):
    """Mesh admission pads each device's row block, not the tail: every
    shard holds floor(n/k) or ceil(n/k) real rows (none holds only padding
    when n >= k), and the real rows keep their order."""
    import numpy as np
    from repro.core.relation import pad_shards, relation
    keys = np.arange(1, n + 1, dtype=np.uint32)
    rel = pad_shards(relation(keys, keys.astype(np.float32)), k, capacity)
    assert rel.capacity == capacity
    valid = np.asarray(rel.valid)
    per_shard = valid.reshape(k, -1).sum(axis=1)
    assert per_shard.max() == -(-n // k) and per_shard.min() == n // k > 0
    assert per_shard.sum() == n
    np.testing.assert_array_equal(np.asarray(rel.keys)[valid], keys)
    np.testing.assert_array_equal(np.asarray(rel.values)[valid],
                                  keys.astype(np.float32))
    assert not np.asarray(rel.keys)[~valid].any()


@pytest.mark.parametrize("k", [4, 8])
def test_stream_window_pads_every_shard(k):
    """A stream on a k-device mesh: short micro-batches are admitted with
    per-device padding (``pad_shards``, as ``place_rows`` does) and the
    session's fused window assembly pads per device again, so a window of
    three short sub-windows in a four-sub-window bucket still gives every
    device real rows, in arrival order.  Tail padding would leave the last
    device holding only padding."""
    import numpy as np
    from repro.core.relation import pad_shards, relation
    from repro.core.window import SubWindow, window_relations
    from repro.runtime.stream_join import _make_window_assemble
    n_subs, sub_cap, rows = 3, 1024, 300
    cap = 4 * sub_cap
    mbs = [relation(np.arange(1 + m * rows, 1 + (m + 1) * rows,
                              dtype=np.uint32)) for m in range(n_subs)]
    subs = [SubWindow(m, (pad_shards(mb, k, sub_cap),) * 2, ("", ""))
            for m, mb in enumerate(mbs)]
    flat = tuple(x for side in range(2) for s in subs for x in s.rels[side])
    got = _make_window_assemble(n_subs, 2, cap, k)(flat)
    want = window_relations(subs, num_shards=k)
    tail = window_relations(subs)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        valid = np.asarray(g.valid)
        assert g.capacity == cap
        assert (valid.reshape(k, -1).sum(axis=1) > 0).all()
        np.testing.assert_array_equal(
            np.asarray(g.keys)[valid], np.arange(1, 1 + n_subs * rows))
    assert not np.asarray(tail[0].valid).reshape(k, -1)[-1].any()


_ADMIT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np, jax
from jax.sharding import Mesh
from repro.core.relation import relation
from repro.core.window import WindowSpec
from repro.runtime.stream_join import StreamJoinServer

def rows_per_device(rel):
    shards = sorted(rel.valid.addressable_shards, key=lambda s: s.index)
    return [int(np.asarray(s.data).sum()) for s in shards]

def keys_in_order(rel, n):
    valid = np.asarray(rel.valid)
    np.testing.assert_array_equal(np.asarray(rel.keys)[valid],
                                  np.arange(1, n + 1))

srv = StreamJoinServer(batch_slots=2,
                       mesh=Mesh(np.array(jax.devices()), ("data",)))
n = 1500                                  # dataset: 1500 rows in a 2048 bucket
ds = relation(np.arange(1, n + 1, dtype=np.uint32))
for rel in srv._admit_rels([ds, ds]):
    assert rel.capacity == 2048 and rows_per_device(rel) == [375] * 4
    keys_in_order(rel, n)
print("DATASET-OK")
sess = srv.open_stream("s", WindowSpec(size=2, slide=2, sub_rows=1024))
mb = relation(np.arange(1, 301, dtype=np.uint32))   # a short micro-batch
got = sess._admit_micro_batch(mb)
assert got.capacity == 1024 and rows_per_device(got) == [75] * 4
keys_in_order(got, 300)
print("STREAM-OK")
"""


def test_mesh_admission_gives_every_device_real_rows():
    """On a 4-device mesh, dataset admission (``JoinServer._admit_rels``)
    and stream admission (``StreamJoinSession._admit_micro_batch``) both
    go through ``relation.place_rows``: every device holds an equal share
    of the real rows, in order, even when the rows fill only part of the
    capacity bucket."""
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", _ADMIT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    for marker in ("DATASET-OK", "STREAM-OK"):
        assert marker in out.stdout, (marker, out.stdout[-2000:])
