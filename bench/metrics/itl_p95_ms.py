"""95th percentile of the gap between consecutive output tokens, over
every gap of every request due in the window (token emission times from
the engine's step timeline); a request that never completed counts all
of its gaps as infinitely late."""
from bench.measure import percentile


def read(run):
    return percentile(run.itl_ms(), 95)
