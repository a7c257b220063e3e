"""Percent of the window in which no executable ran on the device."""
from bench.layers._common import device_idle


def read(ctx):
    return device_idle(ctx)
