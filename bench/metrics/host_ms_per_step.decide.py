"""Engine: mean per step of the Tracer ``decide`` span: prepare's wait to
the sample or exact dispatch (the strata fetch to the host, the exact or
sampled decision and the per-stratum sample sizes)."""


def read(run):
    return run.stage_ms_per_step("decide")
