"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

The trace has one plane per device (``/device:TPU:<n>``) whose ``XLA Ops``
line holds one event per operation run on that device, with its start and
duration in nanoseconds, and a host plane whose ``python`` line holds the
``jax.profiler.TraceAnnotation`` spans.  The harness opens one annotation,
``bench_clock``, at a ``time.perf_counter()`` it records, which puts the
program's own spans (the engine ``Tracer``, on ``perf_counter``) on the
trace's clock.

From the device events inside the traced window this module computes:

* the busy time of each device: the union of its operations' intervals;
* device time by operation name (prefixed with the engine stage span it
  ran in), by kernel name and by collective kind;
* the idle gaps of device 0, each labelled with the engine span open in it.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Iterable, Optional, Sequence

ALIGN = "bench_clock"
OPS_LINE = "XLA Ops"
# the CPU backend has no device plane: the host thread that runs XLA's ops
# stands in for the device (CPU tests of this module only)
CPU_OPS_LINE = "tf_XLAPjRtCpuClient"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
# engine stage spans: every stage executable is a ``jit_fn`` module on the
# device, so an op is named by the stage span its midpoint falls in
STAGES = ("prepare", "sample", "exact", "compile")


def merge(intervals: Iterable[tuple]) -> list:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals: Sequence[tuple], lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[tuple], lo: float, hi: float) -> list:
    """The idle intervals of [lo, hi) around the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class DeviceOps:
    """One device's operations: (event name, start_ns, end_ns, label)."""

    name: str
    ops: list = field(default_factory=list)

    def busy(self, lo: float, hi: float) -> list:
        return merge(clip([(s, e) for _, s, e, _ in self.ops], lo, hi))


@dataclass
class Trace:
    devices: list            # [DeviceOps], ordered by plane name
    align_ns: Optional[float]  # trace time of the bench_clock annotation

    def to_trace_ns(self, pc: float, align_pc: float) -> float:
        return self.align_ns + (pc - align_pc) * 1e9


def _label(name: str) -> str:
    """The op's own name from the HLO text the TPU trace gives as an event
    name (``%fusion.49 = u32[...] fusion(...)`` -> ``fusion.49``); a Pallas
    kernel is a custom call named after its kernel function, marked
    ``[pallas]``."""
    short = name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="tpu_custom_call"' in name:
        short += " [pallas]"
    return short


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, align = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                d = DeviceOps(plane.name)
                for ev in line.events:
                    d.ops.append((ev.name, float(ev.start_ns),
                                  float(ev.start_ns) + float(ev.duration_ns),
                                  _label(ev.name)))
                devices.append(d)
        elif plane.name.startswith("/host:") and align is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ALIGN:
                        align = float(ev.start_ns)
                        break
    if not devices:
        devices = _cpu_devices(pd)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, align)


def _cpu_devices(pd) -> list:
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            if line.name.startswith(CPU_OPS_LINE):
                d = DeviceOps(f"{plane.name}/{line.name}")
                d.ops = [(ev.name, float(ev.start_ns),
                          float(ev.start_ns) + float(ev.duration_ns), ev.name)
                         for ev in line.events
                         if not ev.name.startswith("ThreadpoolListener")]
                out.append(d)
    return out


def inventory(path: str, top: int = 12) -> list:
    """Planes, lines and most frequent event names: for reading a trace
    by hand before trusting a reduction of it."""
    from collections import Counter

    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = Counter(_label(ev.name) for ev in line.events)
            out.append((plane.name, line.name, sum(names.values()),
                        names.most_common(top)))
    return out


@dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace."""

    window_s: float
    busy_s: list             # per device
    op_s: dict               # op label -> seconds, mean over devices
    idle_gaps: list          # [(label, seconds)] longest first, device 0

    @property
    def idle_pct(self) -> float:
        mean_busy = sum(self.busy_s) / len(self.busy_s)
        return 100.0 * (1.0 - mean_busy / self.window_s)

    def seconds(self, match) -> float:
        return sum(s for name, s in self.op_s.items() if match(name))

    def kernel_s(self, kernels: Sequence[str]) -> float:
        """Seconds of the Pallas kernels whose names contain one of
        ``kernels``."""
        return self.seconds(lambda n: n.endswith("[pallas]")
                            and any(k in n for k in kernels))

    def collective_s(self) -> float:
        return self.seconds(lambda n: any(c in n for c in COLLECTIVES))

    def top_ops(self, n: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def _stage_of(spans: Sequence[tuple]):
    """A function from a trace time to the engine stage span holding it
    (stage spans follow one another on the engine's lane)."""
    stages = sorted((s, e, n) for n, s, e in spans if n in STAGES)
    starts = [s for s, _, _ in stages]

    def stage(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < stages[i][1]:
            return stages[i][2] + "/"
        return ""
    return stage


def summarize(trace: Trace, lo_ns: float, hi_ns: float,
              spans: Sequence[tuple] = (), n_gaps: int = 10) -> Summary:
    """Reduce the window [lo_ns, hi_ns) of ``trace``.

    ``spans`` are (name, start_ns, end_ns) engine spans on the trace's
    clock; an op is named ``<stage>/<op>`` after the stage span around its
    midpoint, and a gap is labelled with the innermost (shortest) span
    around its midpoint, or ``no-engine-span`` when none is open."""
    if not trace.devices:
        raise ValueError("trace holds no device operations")
    stage = _stage_of(spans)
    busy = [d.busy(lo_ns, hi_ns) for d in trace.devices]
    op_s: dict = {}
    for d in trace.devices:
        for name, s, e, label in d.ops:
            dt = max(0.0, min(e, hi_ns) - max(s, lo_ns))
            if dt:
                key = stage((s + e) / 2) + label
                op_s[key] = op_s.get(key, 0.0) + dt
    k = len(trace.devices)
    op_s = {n: v / k / 1e9 for n, v in op_s.items()}
    idle = []
    for s, e in gaps(busy[0], lo_ns, hi_ns):
        mid = (s + e) / 2
        inside = [sp for sp in spans if sp[1] <= mid < sp[2]]
        label = min(inside, key=lambda sp: sp[2] - sp[1])[0] if inside \
            else "no-engine-span"
        idle.append((label, (e - s) / 1e9))
    idle.sort(key=lambda g: -g[1])
    return Summary((hi_ns - lo_ns) / 1e9,
                   [sum(e - s for s, e in b) / 1e9 for b in busy],
                   op_s, idle[:n_gaps])
