"""Percent of the window inside the program's exact-verify spans."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "exact-verify")
