"""Percent of the window inside the program's host-order spans
(`jax_sim.scan_order` of each row the row cache missed)."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "host-order")
