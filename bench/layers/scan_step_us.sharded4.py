"""Microseconds of scan-executable device time per sequential scan step,
on device 0 (the lowest-numbered device plane) alone: every scan call
runs there, sharded or not, so its time is a step's time; summed over
the chips a sharded bucket would count once per chip."""
import re


def read(ctx):
    td = ctx["trace"]
    steps = td.sim_steps("device-sim")
    if not td.devices or not steps:
        return None
    first = min(td.devices,
                key=lambda plane: int(re.search(r"(\d+)$", plane).group(1)))
    lo, hi = td.window
    secs = [m.end - m.start for m in td.devices[first]
            if lo <= m.start < hi and m.kind == "scan"]
    if not secs:
        return None
    return 1e6 * sum(secs) / steps
