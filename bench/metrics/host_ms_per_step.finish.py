"""Engine: mean per step of the Tracer ``finish`` span (the last stage's
wait to the step's end: per-query result assembly, sigma feedback, the
server's meters) plus its ``complete`` span (after the step: per-request
bookkeeping and the completion futures with their callbacks)."""

SPANS = ("finish", "complete")


def read(run):
    steps = run.steps()
    spans = [e for e in run.spans if e["name"] in SPANS]
    if not steps or not spans:
        return None
    return 1e3 * sum(e["dur"] for e in spans) / len(steps)
