"""Mean per-chip busy time as a percent of the busiest chip's: 100 when
every chip carried the same work, 100 / chips when one chip did it all."""
from bench.layers._chips import chip_busy


def read(ctx):
    busy = chip_busy(ctx)
    if not busy:
        return None
    return 100.0 * sum(busy) / len(busy) / max(busy)
