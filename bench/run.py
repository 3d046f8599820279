"""One measured run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads[].name``) names a configuration
and a traffic mix; both, and every metric's reader, are files found by name
(``cells.py``).  One process does everything: it checks for the chips the
cell asks for, generates the tables from ``--seed``, builds and warms the
server (set-up), offers the mix's load for ``--seconds`` (the window),
waits for the window's stragglers, frees the server, and compares every
answer due in the window with the dataset's float64 reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the engine's
``Tracer`` and the result carries the per-layer metrics, the device's busy
time and a breakdown of device time and idle gaps.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``checks``: each compared number beside its limit.  The same numbers are
the last lines of stderr.  Without a TPU, or with fewer TPU chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

CACHE_DIR = ROOT / ".jax_cache"
RUNS_DIR = ROOT / ".bench_runs"


def log(msg: str) -> None:
    print(msg, flush=True)


def configure_jax(cache_dir: Path = CACHE_DIR):
    """Keep every compiled executable, also the eager single-op ones that
    compile in well under a second, in the checkout's cache directory.
    Returns the compile-cache log."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from cachelog import CacheLog
    return CacheLog()


def require_chips(chips: int) -> list:
    """The TPU devices, or exit non-zero with no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's first device is "
                         f"{devices[0].platform!r}, not a TPU; no result")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, JAX "
                         f"found {len(devices)}; no result")
    return devices


@dataclass
class Run:
    """What a metric reader reads: the window's requests, the engine's
    counters at the window's ends, its spans and the reduced trace."""

    cell: object
    seconds: float
    t0: float
    t1: float
    setup_s: float
    records: list
    diag0: dict
    diag1: dict
    spans: list                 # engine-lane Tracer events in the window
    trace: Optional[object]     # trace_reduce.Summary

    def steps(self) -> list:
        return [e for e in self.spans if e["name"] == "step"]

    def stage_ms_per_step(self, stage: str) -> Optional[float]:
        steps = self.steps()
        stages = [e for e in self.spans if e["name"] == stage]
        if not steps or not stages:
            return None
        return 1e3 * sum(e["dur"] for e in stages) / len(steps)


def engine_spans(served, t0: float, t1: float) -> list:
    if served.tracer is None:
        return []
    lane = served.engine.trace_name
    return [e for e in list(served.tracer.events)
            if e["tid"] == lane and e["dur"] is not None
            and t0 <= e["ts"] < t1]


def peak_bytes(devices: list) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def reduce_trace(log_dir: Path, align_pc: float, t0: float, t1: float,
                 spans: list, chips: int):
    import trace_reduce as tr
    trace = tr.load(tr.find_xplane(str(log_dir)))
    if trace.align_ns is None:
        raise RuntimeError(f"no {tr.ALIGN} annotation in the trace")
    trace.devices = trace.devices[:chips]
    lo = trace.to_trace_ns(t0, align_pc)
    hi = trace.to_trace_ns(t1, align_pc)
    marks = [(e["name"], trace.to_trace_ns(e["ts"], align_pc),
              trace.to_trace_ns(e["ts"] + e["dur"], align_pc))
             for e in spans]
    return tr.summarize(trace, lo, hi, marks)


def run_cell(cell, seed: int, seconds: float, trace: bool, devices: list,
             cache_log, t_start: float = T_START) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax

    import reference
    import serve

    served = serve.build(cell, seed, devices, trace)
    serve.warm_up(served)
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in served.times.items()))
    compiles0, cache0 = served.diagnostics()["compiles"], cache_log.total()
    trace_dir = RUNS_DIR / f"trace-{cell.name}"
    align_pc = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench_clock"):
            align_pc = time.perf_counter()
    diag0 = served.diagnostics()
    setup_s = time.perf_counter() - t_start
    window = serve.run_window(served, seconds)
    if trace:
        jax.profiler.stop_trace()
    diag1 = served.diagnostics()
    in_window = cache_log.total() - cache0
    engine_compiles = diag1["compiles"] - compiles0
    serve.drain(window, cell.mix.drain_s)
    spans = engine_spans(served, window.t0, window.t1)
    answers = serve.answers(window)
    memory = peak_bytes(devices[:cell.chips])
    tables = served.tables
    served.close()
    del served
    log(f"compile_cache: hits={cache_log.count('hit')} "
        f"misses={cache_log.count('miss')} "
        f"not_kept={cache_log.count('not_kept')}")
    log(f"compiles_in_window: cache_log={in_window} engine={engine_compiles}")
    log(f"memory_peak_bytes: {memory}")
    if window.lateness:
        late = sorted(window.lateness)
        log(f"generator_lateness_s: p50={late[len(late) // 2]!r} "
            f"max={late[-1]!r} n={len(late)}")
    summary = None
    if trace:
        summary = reduce_trace(trace_dir, align_pc, window.t0, window.t1,
                               spans, cell.chips)
    run = Run(cell, seconds, window.t0, window.t1, setup_s, window.records,
              diag0, diag1, spans, summary)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    # the reference runs once the window is closed and the server is gone
    ref = cell.dataset.reference(tables)
    budgets = [r.budget for r in window.records]
    nums = reference.numbers(answers, budgets, ref)
    correct, checks = reference.verdict(nums, cell.mix.checks)
    if in_window or engine_compiles:
        log("compiles inside the window: the run is not steady")
    describe_answers(answers, ref)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory}
    out = {"correct": bool(correct),
           "attempted": len(window.records),
           "failed": sum(a is None for a in answers),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = sum(summary.busy_s) / len(summary.busy_s)
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]]}
    out["checks"] = checks
    return out


def describe_answers(answers: list, ref) -> None:
    sampled = [a for a in answers if a is not None and a["sampled"]]
    if sampled:
        zero = sum(a["bound"] == 0.0 for a in sampled)
        log(f"sampled_answers: n={len(sampled)} bound_zero={zero}")
    overflow = sum(a["strata_overflow"] for a in answers if a is not None)
    log(f"reference: sum={ref.total!r} count={ref.count} "
        f"strata_overflow_total={overflow}")


def print_result(out: dict) -> None:
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("bench: --seed must be non-negative")
    from cells import load_cell
    cell = load_cell(args.workload, ROOT)
    cache_log = configure_jax()
    devices = require_chips(cell.chips)
    log(f"device: kind={devices[0].device_kind} count={len(devices)} "
        f"cell={cell.name} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   cache_log)
    print_result(out)
    sys.stderr.flush()
    # the TPU runtime's shutdown adds seconds and does nothing for the
    # result; every thread this run started has ended
    os._exit(0)


if __name__ == "__main__":
    main()
