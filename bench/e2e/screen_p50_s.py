"""Median latency of unverified (screening) answers over every request
of the window, from when it was due to its answer."""
from bench.e2e._common import latencies, percentile


def read(ctx):
    return percentile(latencies(ctx), 50)
