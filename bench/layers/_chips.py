"""Per-chip readings of a trace taken on several chips."""
from __future__ import annotations


def chip_busy(ctx) -> list:
    """Busy seconds in the window, per chip of the cell; a chip that ran
    no executable, and so left no line in the trace, reads 0. Empty
    where no chip ran one."""
    busy = ctx["trace"].busy()
    if not any(busy):
        return []
    return busy + [0.0] * (ctx["chips"] - len(busy))
