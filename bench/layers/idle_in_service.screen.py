"""Percent of the window in which the device runs nothing while the
server holds at least one question (a ``request|serve`` span is open)."""
from bench.layers._serve import idle_in_service


def read(ctx):
    return idle_in_service(ctx)
