"""Engine: mean per step of the Tracer ``inputs`` span: the step's start to
the prepare dispatch (stacking the batch's relations, filter words and
seeds; the stage builders; the executable lookup)."""


def read(run):
    return run.stage_ms_per_step("inputs")
