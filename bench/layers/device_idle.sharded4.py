"""Percent of the window in which a chip ran no executable, averaged
over the cell's chips: 1 - mean per-chip busy / window."""
from bench.layers._chips import chip_busy


def read(ctx):
    busy = chip_busy(ctx)
    if not busy:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx["trace"].window_s)
