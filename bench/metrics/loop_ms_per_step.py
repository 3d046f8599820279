"""Async tier: mean per step of the event loop's own spans: ``drain``
(ingress ring -> engine admission), ``linger`` (slot backfill before a
step) and ``idle`` (waiting on an empty queue).

A program whose loop records no ``drain`` span does not trace its loop,
and this reads nothing there (``linger`` alone would read low)."""

SPANS = ("drain", "linger", "idle")


def read(run):
    steps = run.steps()
    spans = [e for e in run.spans if e["name"] in SPANS]
    if not steps or not any(e["name"] == "drain" for e in spans):
        return None
    return 1e3 * sum(e["dur"] for e in spans) / len(steps)
