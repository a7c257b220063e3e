"""Shared arithmetic of the per-layer readers: shares of the window and
the least-time byte count of the simulation kernels."""
from __future__ import annotations

# bytes one simulated op row brings in: res and cls (i32), nbytes, reqs,
# extra and nlat (f64), MAXD = 4 dependency ids (i32)
OP_BYTES = 4 + 4 + 4 * 8 + 4 * 4
# per candidate row: the 7 service times in, the makespan out
ROW_BYTES = 7 * 8 + 8


def phase_share(ctx, phase):
    """Percent of the window inside the program's spans of one phase,
    or None where the window has none."""
    td = ctx["trace"]
    if not td.phase_spans(phase):
        return None
    return 100.0 * td.phase_share(phase)


def device_idle(ctx):
    td = ctx["trace"]
    if not td.modules():
        return None
    return 100.0 * (1.0 - td.busy_s() / td.window_s)


def rows(ctx, mode):
    """(workflow, layout) of every row the window simulated in one mode:
    ``scan`` is every candidate of every answer, ``exact`` the verified
    ones."""
    out = []
    for o in ctx["outcomes"]:
        if o.error:
            continue
        for idx, _, _, verified in o.ranked:
            if mode == "scan" or verified:
                out.append((o.req.workflows[idx], o.req.layouts[idx]))
    return out


def least_bytes(ctx, mode):
    refs = ctx["refs"]
    return sum(refs.count(wf, lay) * OP_BYTES + ROW_BYTES
               for wf, lay in rows(ctx, mode))


def hbm_bytes_per_s(ctx):
    kind = ctx["device_kind"]
    table = ctx["peaks"]["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device {kind!r} in bench/peaks.json")
    return table[kind]["hbm_bytes_per_s"]


def roofline(ctx, mode):
    """Percent of the least time (bytes over peak HBM bandwidth) in the
    device time of the window's executables of one mode."""
    mods = ctx["trace"].modules(mode)
    if not mods:
        return None
    device_s = sum(m.end - m.start for m in mods)
    return 100.0 * least_bytes(ctx, mode) / hbm_bytes_per_s(ctx) / device_s
