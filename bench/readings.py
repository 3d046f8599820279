"""Readings for a cell's limits: the program's compared numbers over many
seeds, and the control's beside them (run by hand on the chip; the
benchmark's own runs never run it).

    python3 bench/readings.py --workload <cell> --seeds 101,102,... \
        --seconds 10 [--out readings.jsonl]

One process.  For each seed it generates the tables, builds and warms the
server, runs a window of ``--seconds`` at the cell's own load, waits for
every answer, and computes the numbers ``run.py`` compares.  It then puts
the control in the program's place for the same requests:

* exact budgets: the reference computed in bfloat16 on the chip
  (``reference.control_exact_bf16``);
* error budgets: the reference's own sample drawn at 68% confidence where
  the configuration states 95% (``reference.control_sampled``).

A mix may hold both kinds; each request gets the control of its own.

Each seed prints one JSON line with both sets of numbers.  The lower
reading of a number is the largest the program gives over the seeds; the
upper is the smallest the control gives.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

import run
import reference
import serve
from cells import load_cell


def control_answers(cell, tables, ref, budgets: list, seed: int) -> list:
    joined = cell.dataset.joined(tables)
    rng = np.random.default_rng([int(seed), 3])
    exact = (reference.control_exact_bf16(joined)
             if None in budgets else None)
    return [dict(exact) if e is None else
            reference.control_sampled(joined, e, ref, rng) for e in budgets]


def one_seed(cell, seed: int, seconds: float, devices: list) -> dict:
    served = serve.build(cell, seed, devices, trace=False)
    serve.warm_up(served)
    window = serve.run_window(served, seconds)
    serve.drain(window, cell.mix.drain_s)
    answers = serve.answers(window)
    tables = served.tables
    served.close()
    del served
    gc.collect()
    ref = cell.dataset.reference(tables)
    budgets = [r.budget for r in window.records]
    return {"seed": seed, "answers": len(answers),
            "program": reference.numbers(answers, budgets, ref),
            "control": reference.numbers(
                control_answers(cell, tables, ref, budgets, seed),
                budgets, ref)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    run.configure_jax()
    devices = run.require_chips(cell.chips)
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = one_seed(cell, seed, args.seconds, devices)
        line = json.dumps({"workload": cell.name, **row})
        print(line, flush=True)
        if out is not None:
            out.write(line + "\n")
            out.flush()
    if out is not None:
        out.close()
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
