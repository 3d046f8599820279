"""Engine: real requests per step over the batch slots, from the Tracer's
``step`` spans in the window."""


def read(run):
    steps = run.steps()
    if not steps:
        return None
    slots = int(run.cell.config["engine"]["batch_slots"])
    return 100.0 * sum(e["args"]["batch"] for e in steps) / (
        slots * len(steps))
