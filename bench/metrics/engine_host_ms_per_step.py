"""Engine: mean per step of the Tracer ``step`` span less its ``prepare``,
``sample`` and ``exact`` stage spans: the host's own work in a step."""


def read(run):
    steps = run.steps()
    if not steps:
        return None
    stage = sum(e["dur"] for e in run.spans
                if e["name"] in ("prepare", "sample", "exact"))
    return 1e3 * (sum(e["dur"] for e in steps) - stage) / len(steps)
