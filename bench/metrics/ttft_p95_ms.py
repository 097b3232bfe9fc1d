"""95th percentile of time to first token over every request due in the
window, from its due time; a request that never completed counts as
infinitely late."""
from bench.measure import percentile


def read(run):
    return percentile(run.ttft_ms(), 95)
