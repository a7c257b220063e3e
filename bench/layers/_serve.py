"""Readers over the program's serve spans: how long an admitted
question waits for the dispatcher, and how long the device idles while
the server holds a question."""
from __future__ import annotations

from bench.e2e._common import percentile
from bench.tracedata import clip, covered


def _queue_waits(ctx):
    """Durations of the ``queued|serve-queue`` spans that start in the
    window."""
    td = ctx["trace"]
    lo, hi = td.window
    return [s.end - s.start for s in td.spans
            if (s.name, s.phase) == ("queued", "serve-queue")
            and lo <= s.start < hi]


def queue_wait_mean_s(ctx):
    """Mean queue wait, or None where there is none. For the advisor's
    few dozen waits, which are bimodal (behind a longer question, or at
    an idle server): a median lands on either mode by the seed's
    order."""
    waits = _queue_waits(ctx)
    return sum(waits) / len(waits) if waits else None


def queue_wait_p50_s(ctx):
    """Nearest-rank median queue wait, or None where there is none. For
    the scan's hundreds of short waits, where one host stall queues a
    few dozen questions for a second and would swing a mean tenfold."""
    waits = _queue_waits(ctx)
    return percentile(waits, 50) if waits else None


def idle_in_service(ctx):
    """Percent of the window in which the first device runs no
    executable while at least one ``request|serve`` span is open, or
    None where the window has no such span."""
    td = ctx["trace"]
    lo, hi = td.window
    served = clip([[s.start, s.end] for s in td.spans
                   if (s.name, s.phase) == ("request", "serve")], lo, hi)
    if not served or not td.devices:
        return None
    mods = next(iter(td.devices.values()))
    busy = clip([[m.start, m.end] for m in mods], lo, hi)
    # |served \ busy| = |served u busy| - |busy|
    return 100.0 * (covered(served + busy) - covered(busy)) / td.window_s
