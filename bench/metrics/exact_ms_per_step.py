"""Stages: mean per step of the Tracer ``exact`` span (per-stratum sums of
the whole join)."""


def read(run):
    return run.stage_ms_per_step("exact")
