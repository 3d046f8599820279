"""Kernels: device time per step of the Bloom probe's Pallas kernel
(``kernels/bloom_probe.py``), from the profiler trace.  On a TPU v5e it
is a custom call named ``probe_filter_batched.<n>``."""

KERNELS = ("probe_filter_batched",)


def read(run):
    steps = run.steps()
    if run.trace is None or not steps:
        return None
    s = run.trace.kernel_s(KERNELS)
    return 1e3 * s / len(steps) if s > 0 else None
