"""Host process: mean per step of the Tracer ``gc`` spans, one for each
garbage collection of the process, whichever thread ran it (a collection
holds the interpreter lock, so it stops the engine's thread too).

A window with no collection reads 0.  A program that records no
engine-phase spans (``inputs``) records no collections either, and this
reads nothing there."""


def read(run):
    steps = run.steps()
    if not steps or not any(e["name"] == "inputs" for e in run.spans):
        return None
    return 1e3 * sum(e["dur"] for e in run.spans
                     if e["name"] == "gc") / len(steps)
