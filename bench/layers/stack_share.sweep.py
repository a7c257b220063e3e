"""Percent of the window inside the program's host-stack spans (the
host's part of stacking a bucket batch and copying its service times)."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "host-stack")
