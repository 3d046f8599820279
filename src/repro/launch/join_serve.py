"""JoinServer driver: multi-tenant batched ApproxJoin serving.

Builds synthetic tenant datasets in several capacity shape classes,
registers them as named handles, submits an interleaved query stream
(error-budget, latency-budget, and exact tenants), and prints throughput
plus the server's executable-cache / batching / filter-cache diagnostics.

Usage:
  PYTHONPATH=src python -m repro.launch.join_serve --tenants 4 \
      --queries-per-tenant 8 --slots 4

  # distributed: one batched step spans all mesh devices
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.join_serve --mesh 8

  # always-on async tier: event-loop replicas, continuous batching,
  # tenant sharding + work stealing behind one front door
  PYTHONPATH=src python -m repro.launch.join_serve --async --replicas 2

``--mesh N`` serves through the shard_map pipeline over the first N
devices.  On the CPU (``JAX_PLATFORMS=cpu``) it re-execs under
``--xla_force_host_platform_device_count`` when needed (the flag must be set
before jax initializes); on an accelerator a mesh larger than the host is an
error.
"""

from __future__ import annotations

import argparse
import subprocess
import sys
import time

from repro.core.budget import QueryBudget
from repro.core.cost import CostModel
from repro.data.synthetic import overlapping_relations
from repro.launch.platform import (configure_compile_cache, cpu_device_env,
                                   mesh_devices as _mesh_devices)
from repro.runtime.async_serve import AsyncJoinFrontDoor
from repro.runtime.fault import InjectedFault
from repro.runtime.join_serve import JoinRequest, JoinServer
from repro.runtime.telemetry import (Tracer, dump_chrome_trace,
                                     format_reconciliation,
                                     reconciliation_report)


def run(*, tenants: int = 4, queries_per_tenant: int = 8, slots: int = 4,
        base_n: int = 1 << 12, seed: int = 0, mesh_devices: int = 0,
        serve_mode: str = "exact-parity",
        trace_out: str | None = None) -> dict:
    mesh = None
    if mesh_devices:
        import numpy as np
        from jax.sharding import Mesh
        mesh = Mesh(np.array(_mesh_devices(mesh_devices)), ("data",))
    tracer = Tracer(enabled=True) if trace_out else None
    server = JoinServer(batch_slots=slots,
                        cost_model=CostModel(beta_compute=1e-7, epsilon=1e-3),
                        mesh=mesh, serve_mode=serve_mode, tracer=tracer)
    budgets = [QueryBudget(error=0.5), QueryBudget(latency_s=0.5),
               QueryBudget()]
    for t in range(tenants):
        n = base_n << (t % 2)          # two capacity shape classes
        rels = overlapping_relations([n, n], 0.1, seed=seed + t)
        server.register_dataset(f"tenant{t}", rels)

    reqs = []
    for q in range(queries_per_tenant):
        for t in range(tenants):       # interleave tenants (worst case)
            reqs.append(server.submit(JoinRequest(
                dataset=f"tenant{t}", budget=budgets[t % len(budgets)],
                query_id=f"tenant{t}/agg", seed=seed + q,
                max_strata=2048, b_max=512)))
    t0 = time.perf_counter()
    server.run()
    dt = time.perf_counter() - t0

    d = server.diagnostics
    qps = d.queries / max(dt, 1e-9)
    where = f"mesh[{mesh_devices}]" if mesh_devices else "single-device"
    print(f"[join-serve] {d.queries} queries from {tenants} tenants in "
          f"{dt:.2f}s ({qps:.1f} q/s) on {where}")
    print(f"  steps={d.steps} max_batch={d.max_batch} "
          f"compiles={d.compiles} cache_hits={d.cache_hits}")
    print(f"  exact={d.exact_queries} sampled={d.sampled_queries} "
          f"mean_queue_latency={d.queue_latency_s / max(d.queries, 1):.3f}s")
    print(f"  filter_builds={d.filter_builds} "
          f"filter_cache_hits={d.filter_cache_hits} "
          f"shuffled_bytes_saved={d.shuffled_bytes_saved:.0f}")
    if mesh_devices:
        per_dev = [f"{b:.0f}" for b in d.per_device_shuffled_bytes]
        print(f"  dist_shuffled_tuple_bytes={d.dist_shuffled_tuple_bytes:.0f}"
              f" per_device={per_dev}")
        print(f"  serve_mode={serve_mode} "
              f"wire_bytes_model={d.dist_wire_bytes_model:.0f} "
              f"dropped_tuples={d.dist_dropped_tuples:.0f}")
    for r in reqs[:3]:
        print(f"  {r.query_id}: estimate={float(r.result.estimate):.1f} "
              f"+-{float(r.result.error_bound):.1f} "
              f"sampled={bool(r.result.diagnostics.sampled)}")
    if trace_out:
        recon = server.reconciliation_report()
        n_ev = dump_chrome_trace(tracer, trace_out, reconciliation=recon)
        print(f"  trace: {n_ev} events -> {trace_out} (open in "
              "ui.perfetto.dev or chrome://tracing)")
        print(format_reconciliation(recon))
    return {"queries": d.queries, "seconds": dt, "qps": qps,
            **d.snapshot()}


def run_async(*, tenants: int = 4, queries_per_tenant: int = 8,
              slots: int = 4, base_n: int = 1 << 12, seed: int = 0,
              replicas: int = 2, mesh_devices: int = 0,
              serve_mode: str = "exact-parity",
              checkpoint_dir: str | None = None,
              kill_after: int = 0,
              trace_out: str | None = None) -> dict:
    """The same tenant workload through the always-on async tier: replica
    event loops with continuous batching behind a work-stealing front door
    (``runtime/async_serve.py``); submissions return futures immediately.

    ``checkpoint_dir`` turns on per-replica engine checkpointing;
    ``kill_after`` N > 0 additionally runs the fault drill — replica0 dies
    (``InjectedFault``) after N served steps, the front door fails it over,
    and a successor adopts its tenants from the newest checkpoint.  Futures
    that were in flight on the dead replica fail with the injected fault
    (counted below); their requests are re-served from the checkpoint by
    the successor."""
    def factory(i: int) -> JoinServer:
        mesh = None
        if mesh_devices:
            import numpy as np
            from jax.sharding import Mesh
            mesh = Mesh(np.array(_mesh_devices(mesh_devices)), ("data",))
        return JoinServer(batch_slots=slots,
                          cost_model=CostModel(beta_compute=1e-7,
                                               epsilon=1e-3),
                          mesh=mesh, serve_mode=serve_mode)

    budgets = [QueryBudget(error=0.5), QueryBudget(latency_s=0.5),
               QueryBudget()]
    tracer = Tracer(enabled=True) if trace_out else None
    with AsyncJoinFrontDoor(replicas=replicas, engine_factory=factory,
                            checkpoint_dir=checkpoint_dir,
                            tracer=tracer) as fd:
        for t in range(tenants):
            n = base_n << (t % 2)      # two capacity shape classes
            rels = overlapping_relations([n, n], 0.1, seed=seed + t)
            fd.register_dataset(f"tenant{t}", rels)
        t0 = time.perf_counter()
        if kill_after:
            # arm before submitting: the drill must fire mid-workload, not
            # race a drained queue (work stealing can empty replica0 fast)
            fd.replicas[0].kill_after(kill_after)
        futs = []
        for q in range(queries_per_tenant):
            for t in range(tenants):   # interleave tenants (worst case)
                futs.append(fd.submit(JoinRequest(
                    dataset=f"tenant{t}", budget=budgets[t % len(budgets)],
                    query_id=f"tenant{t}/agg", seed=seed + q,
                    max_strata=2048, b_max=512)))
        reqs, killed = [], 0
        for f in futs:
            try:
                reqs.append(f.result(timeout=600))
            except InjectedFault:
                if not kill_after:
                    raise
                killed += 1
        if kill_after:
            fd.maybe_failover()
            # re-served-from-checkpoint requests carry no caller futures:
            # wait for the successor to drain its adopted queue
            deadline = time.monotonic() + 600
            while any(r.backlog() for r in fd.replicas
                      if r.error is None) and time.monotonic() < deadline:
                time.sleep(0.01)
        dt = time.perf_counter() - t0
        snap = fd.snapshot()

    qps = len(reqs) / max(dt, 1e-9)
    where = f"mesh[{mesh_devices}]" if mesh_devices else "single-device"
    print(f"[join-serve --async] {len(reqs)} queries from {tenants} tenants "
          f"in {dt:.2f}s ({qps:.1f} q/s) on {where} x{replicas} replicas "
          f"steals={snap['steals']}")
    if kill_after:
        print(f"  fault drill: killed replica0 after {kill_after} steps; "
              f"failovers={snap['failovers']} futures_failed={killed} "
              f"(re-served from checkpoint by the successor)")
    for name, rd in snap["replicas"].items():
        print(f"  {name}: queries={rd['queries']} steps={rd['steps']} "
              f"max_batch={rd['max_batch']} backfilled={rd['backfilled']} "
              f"stolen_in={rd['stolen_in']} "
              f"queue_p95={rd['queue_latency_p95_s']:.3f}s "
              f"e2e_p95={rd['e2e_latency_p95_s']:.3f}s")
    for r in reqs[:3]:
        print(f"  {r.query_id}: estimate={float(r.result.estimate):.1f} "
              f"+-{float(r.result.error_bound):.1f} "
              f"sampled={bool(r.result.diagnostics.sampled)}")
    if trace_out:
        # fleet-level report: the shared tracer holds every replica's
        # per-query recon records; server-level byte pairs are per-engine,
        # so the fleet dump aggregates queries only
        recon = reconciliation_report(tracer.recon)
        n_ev = dump_chrome_trace(tracer, trace_out, reconciliation=recon)
        print(f"  trace: {n_ev} events -> {trace_out} (open in "
              "ui.perfetto.dev or chrome://tracing)")
        print(format_reconciliation(recon))
    return {"queries": len(reqs), "seconds": dt, "qps": qps, **snap}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--queries-per-tenant", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--base-n", type=int, default=1 << 12)
    ap.add_argument("--mesh", type=int, default=0,
                    help="serve distributed over N devices (0 = off)")
    ap.add_argument("--serve-mode", default="exact-parity",
                    choices=["exact-parity", "psum"],
                    help="mesh merge strategy: bit-parity gather vs "
                         "capacity-planned psum")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="serve through the async tier (event-loop "
                         "replicas + front door) instead of the step loop")
    ap.add_argument("--replicas", type=int, default=2,
                    help="front-door replica event loops (with --async)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="per-replica engine checkpointing directory "
                         "(with --async): crash-safe serving state")
    ap.add_argument("--kill-after", type=int, default=0,
                    help="fault drill (with --async + --checkpoint-dir): "
                         "kill replica0 after N served steps and fail its "
                         "tenants over to a successor")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-query span trees and write a Chrome "
                         "trace-event JSON (perfetto-viewable) plus a "
                         "modeled-vs-measured byte reconciliation report; "
                         "summarize with repro.launch.trace_dump")
    args = ap.parse_args()
    if args.kill_after and not (args.async_ and args.checkpoint_dir):
        ap.error("--kill-after needs --async and --checkpoint-dir")
    env = cpu_device_env(args.mesh) if args.mesh else None
    if env is not None:
        raise SystemExit(subprocess.call(
            [sys.executable, "-m", "repro.launch.join_serve",
             *sys.argv[1:]], env=env))
    configure_compile_cache()
    if args.async_:
        run_async(tenants=args.tenants,
                  queries_per_tenant=args.queries_per_tenant,
                  slots=args.slots, base_n=args.base_n,
                  replicas=args.replicas, mesh_devices=args.mesh,
                  serve_mode=args.serve_mode,
                  checkpoint_dir=args.checkpoint_dir,
                  kill_after=args.kill_after, trace_out=args.trace_out)
    else:
        run(tenants=args.tenants,
            queries_per_tenant=args.queries_per_tenant,
            slots=args.slots, base_n=args.base_n, mesh_devices=args.mesh,
            serve_mode=args.serve_mode, trace_out=args.trace_out)


if __name__ == "__main__":
    main()
