"""Percent of the window inside the program's host-pack spans (padding,
permuting and copying each row the row cache missed)."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "host-pack")
