"""Median of due time -> result over every request due in the window."""

from traffic import latencies, percentile


def read(run):
    lat = latencies(run.records)
    return percentile(lat, 50) if lat else None
