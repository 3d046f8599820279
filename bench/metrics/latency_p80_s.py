"""80th percentile of due time -> result over every request due in the
window: the highest percentile with ten or more of a window's ~50 requests
beyond it."""

from traffic import latencies, percentile


def read(run):
    lat = latencies(run.records)
    return percentile(lat, 80) if lat else None
