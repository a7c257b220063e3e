"""Microseconds of scan-executable device time per sequential scan step:
the op bucket of every scan call the window made."""


def read(ctx):
    td = ctx["trace"]
    mods = td.modules("scan")
    steps = td.sim_steps("device-sim")
    if not mods or not steps:
        return None
    return 1e6 * sum(m.end - m.start for m in mods) / steps
