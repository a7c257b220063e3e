"""Percent of the scan executables' device time that the least time of
their work takes: each real row's inputs read once and its makespan
written once, at peak HBM bandwidth."""
from bench.layers._common import roofline


def read(ctx):
    return roofline(ctx, "scan")
