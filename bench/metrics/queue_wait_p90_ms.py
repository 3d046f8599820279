"""Async tier: p90 of the engine's ingest -> dispatch wait
(``JoinRequest.queue_latency_s``) over the window's answered requests."""

from traffic import percentile


def read(run):
    waits = [1e3 * r.request.queue_latency_s for r in run.records
             if r.done is not None]
    return percentile(waits, 90) if waits else None
