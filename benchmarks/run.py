"""Run every paper-figure benchmark and print one CSV.

  PYTHONPATH=src python -m benchmarks.run            # all, full scale
  PYTHONPATH=src python -m benchmarks.run fig01 ...  # subset by prefix
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI gate: every figure
                                                     # end-to-end, small scale
"""

from __future__ import annotations

import argparse
import importlib
import os
import time

MODULES = [
    ("fig01", "fig01_sampling_strategies"),
    ("fig04", "fig04_shuffle_models"),
    ("fig05", "fig05_cost_function"),
    ("fig08", "fig08_twoway_filtering"),
    ("fig09", "fig09_multiway"),
    ("fig10", "fig10_sampling_benefits"),
    ("fig11", "fig11_budget_fidelity"),
    ("fig12", "fig12_tpch"),
    ("fig13", "fig13_realworld"),
    ("fig14", "fig14_fp_tradeoff"),
    ("fig15", "fig15_bloom_variants"),
    ("kernels", "kernels_bench"),
    ("serve", "serve_bench"),
    ("stream", "stream_bench"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("figs", nargs="*", help="subset of figures, by prefix")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-scale end-to-end run of every figure")
    args = ap.parse_args()
    if args.smoke:
        # must land before the figure modules (and benchmarks.common) import
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from benchmarks.common import print_rows
    from repro.launch.platform import configure_compile_cache
    configure_compile_cache()

    failures = []
    for name, modname in MODULES:
        if args.figs and not any(name.startswith(w) for w in args.figs):
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{modname}")
            rows = mod.run()
            print_rows(rows)
            print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, e))
            print(f"# {name}: FAILED {type(e).__name__}: {e}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} benchmark(s) failed: "
                         f"{[n for n, _ in failures]}")


if __name__ == "__main__":
    main()
