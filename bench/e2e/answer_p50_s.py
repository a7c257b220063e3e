"""Median answer latency over every request of the window, from when it
was due (open loop) or sent (closed loop) to its answer."""
from bench.e2e._common import latencies, percentile


def read(ctx):
    return percentile(latencies(ctx), 50)
