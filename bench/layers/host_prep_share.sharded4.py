"""Percent of the window inside the program's host-prep spans
(`scan_order`, padding, stacking, the copy to the devices)."""
from bench.layers._common import phase_share


def read(ctx):
    return phase_share(ctx, "host-prep")
