"""Is what the window served correct? The answers, against the
benchmark's plain reference (`bench.reference`), after the window has
closed and the program's state is freed.

Numbers compared, each with its limit (the mix's ``check.limits``):

    missing       answers that never came (the request raised)      0
    order_faults  answers whose ranking is not what `explore`
                  promises: every candidate once, sorted by
                  makespan, the ``verify_top_k`` best by scan
                  verified, the rest left at their scan makespan    0
    scan_gap      widest relative gap of a served scan makespan
                  from the reference scan
    exact_gap     widest relative gap of a served verified makespan
                  from the reference discrete-event simulation

A makespan can jump where two events nearly tie: a perturbation of the
inputs at rounding size puts them in the other order. Such a row's
served makespan may take either value. So a row's gap over its limit is
read again against the reference under ``TIE_TRIES`` seeded
perturbations of the service times by ``TIE_EPS`` relative, and the
least gap counts (until one row stays over, after which a run is not
correct whatever the others read).

The ranking is checked on every answer; the makespans on every row of
every answer, or of the ``check.requests`` answers drawn from the seed
where the mix sets it.
"""
from __future__ import annotations

import json

import numpy as np

from .reference.compile import compile_dag
from .reference.sim import exact_makespan, scan_makespan

HUGE = 1e300
# three times the widest rounding gap of the chip's emulated f64 from
# IEEE f64 in the exact simulation (3.4e-14, measured on a TPU v5e)
TIE_EPS = 1e-13
TIE_TRIES = 64


def rel_gap(got: float, want: float) -> float:
    d = abs(got - want) / max(abs(want), 1e-300)
    return d if d <= HUGE else HUGE


class RefDags:
    """Reference DAGs, compiled once per distinct (workflow, layout)."""

    def __init__(self):
        self.dags: dict = {}

    def dag(self, wf, lay) -> dict:
        k = json.dumps([wf, lay], sort_keys=True)
        if k not in self.dags:
            self.dags[k] = compile_dag(wf, lay)
        return self.dags[k]

    def count(self, wf, lay) -> int:
        """Op rows of one candidate: its unpadded op count."""
        return len(self.dag(wf, lay)["res"])


def order_faults(o) -> int:
    n = len(o.req.layouts)
    idx = sorted(r[0] for r in o.ranked)
    if idx != list(range(n)):
        return 1
    ms = [r[1] for r in o.ranked]
    if any(not a <= b for a, b in zip(ms, ms[1:])):
        return 1
    ver = [r for r in o.ranked if r[3]]
    rest = [r for r in o.ranked if not r[3]]
    if len(ver) != min(o.req.verify_top_k, n):
        return 1
    if ver and rest and max(r[2] for r in ver) > min(r[2] for r in rest):
        return 1
    if any(r[1] != r[2] for r in rest):
        return 1
    return 0


class Gaps:
    """Widest tie-tolerant gap of served makespans from one reference."""

    def __init__(self, sim, limit, rng):
        self.sim, self.limit, self.rng = sim, limit, rng
        self.widest = 0.0
        self.retry = True

    def add(self, served, dag, st) -> None:
        gap = rel_gap(served, self.sim(dag, st))
        for _ in range(TIE_TRIES if self.retry else 0):
            if gap <= self.limit:
                break
            moved = {k: v * (1 + TIE_EPS * self.rng.standard_normal())
                     for k, v in st.items()}
            gap = min(gap, rel_gap(served, self.sim(dag, moved)))
        self.retry = self.retry and gap <= self.limit
        self.widest = max(self.widest, gap)


def check(outcomes, mix: dict, seed: int, refs: RefDags) -> tuple:
    """({name: (value, limit)}, rows compared) for one window."""
    chk = mix["check"]
    limits = chk["limits"]
    rng = np.random.default_rng([seed, 0x636865636B])
    done = [o for o in outcomes if not o.error]
    out = {"missing": (len(outcomes) - len(done), 0),
           "order_faults": (sum(order_faults(o) for o in done), 0)}
    picked = done
    if chk["requests"] and len(done) > chk["requests"]:
        picked = [done[i] for i in sorted(rng.choice(
            len(done), chk["requests"], replace=False))]
    scan = Gaps(scan_makespan, limits["scan_gap"], rng)
    exact = Gaps(exact_makespan, limits.get("exact_gap", 0.0), rng)
    rows = 0
    for o in picked:
        if order_faults(o):
            continue                      # counted under order_faults
        for i, ms, scan_ms, verified in o.ranked:
            dag = refs.dag(o.req.workflows[i], o.req.layouts[i])
            scan.add(scan_ms, dag, o.req.st)
            if verified:
                exact.add(ms, dag, o.req.st)
            rows += 1
    out["scan_gap"] = (scan.widest, limits["scan_gap"])
    if any(o.req.verify_top_k for o in outcomes):
        out["exact_gap"] = (exact.widest, limits["exact_gap"])
    return out, rows
