"""Find the highest rate a cell's server sustains (run once, by hand).

    python3 bench/capacity.py --workload <cell> --seed <n> --seconds <s> \
        --clients 8 --rates 2.5,3.0   # or --rates auto: 0.8x, 1.2x

One process: set-up as in ``run.py``, then a closed loop of ``--clients``
callers for ``--seconds`` (its throughput is the saturation rate), then an
open loop at each of ``--rates`` for ``--seconds``.  Every second it prints
the backlog (requests queued in the server); a rate the server sustains
keeps it flat, one above capacity makes it grow all through the window.
The open-loop rate in a traffic file is set from what this prints.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import run  # noqa: F401  (puts bench/ and src/ on the path)
import serve
from cells import load_cell
from traffic import latencies, percentile


def sample_backlog(served, stop: threading.Event, out: list) -> None:
    t0 = time.perf_counter()
    while not stop.wait(1.0):
        out.append((round(time.perf_counter() - t0, 1),
                    served.server.backlog()))


def measure(served, seconds: float, **loop) -> dict:
    stop, backlog = threading.Event(), []
    sampler = threading.Thread(target=sample_backlog,
                               args=(served, stop, backlog))
    sampler.start()
    window = serve.run_window(served, seconds, **loop)
    stop.set()
    sampler.join()
    serve.drain(window, served.cell.mix.drain_s)
    lat = latencies(window.records)
    done = sum(1 for r in window.records
               if r.done is not None and r.done <= window.t1)
    return {**loop, "attempted": len(window.records),
            "qps": done / seconds,
            "latency_p50_s": percentile(lat, 50) if lat else None,
            "latency_p90_s": percentile(lat, 90) if lat else None,
            "backlog": backlog}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    run.configure_jax()
    devices = run.require_chips(cell.chips)
    served = serve.build(cell, args.seed, devices, trace=False)
    serve.warm_up(served)
    rows = [measure(served, args.seconds, loop="closed",
                    clients=args.clients)]
    print(json.dumps(rows[-1]), flush=True)
    sat = rows[-1]["qps"]
    rates = ([0.8 * sat, 1.2 * sat] if args.rates == "auto" else
             [float(r) for r in args.rates.split(",") if r])
    for rate in rates:
        rows.append(measure(served, args.seconds, loop="open", rate=rate))
        print(json.dumps(rows[-1]), flush=True)
    served.close()
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
