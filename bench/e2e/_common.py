"""Shared arithmetic of the end-to-end readers."""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: a latency that was observed."""
    v = sorted(values)
    return v[max(math.ceil(q / 100 * len(v)) - 1, 0)]


def latencies(ctx) -> list:
    """Every answer's latency in the window; a failed answer counts as
    missing every limit, an infinite latency."""
    return [o.latency if not o.error else math.inf for o in ctx["outcomes"]]
