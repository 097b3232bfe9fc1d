"""Scheduler: 95th percentile of due time to first admission into a
slot, over the window's requests (the engine's admission stamps)."""
from bench.measure import percentile


def read(run):
    return percentile(run.queue_wait_ms(), 95)
