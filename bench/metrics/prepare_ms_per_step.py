"""Stages: mean per step of the Tracer ``prepare`` span (Bloom probe, sort,
strata; on a mesh also the key shuffle)."""


def read(run):
    return run.stage_ms_per_step("prepare")
