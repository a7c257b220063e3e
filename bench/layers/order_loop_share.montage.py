"""Percent of the window inside the program's host-order-loop spans: the
`jax_sim.scan_order` calls that the per-op loop makes, for DAGs too
small, deep or narrow for the level pass."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "host-order-loop")
