"""What every driver shares: the outcome of one request, the program's
session on the cell's chips, and the plain-to-program conversions.

A driver is ``bench/drivers/<name>.py`` with a class ``Driver(cell,
gen, *, tracer, dag_dir, hooks)`` whose ``run(seconds)`` warms up every
shape the window uses (`generator.Generator.warmup`), sets
``setup_done``, calls ``hooks.window_open()``, serves requests from
``gen.next()`` for ``seconds``, calls ``hooks.window_close()`` and
leaves ``outcomes`` and ``window`` (its perf_counter bounds). A request
due at ``due`` seconds after the window opened has its latency counted
from then; one of a closed loop (``due`` None) from when it was sent.
"""
from __future__ import annotations

import gc
from dataclasses import dataclass, field

from bench import workflows as W


@dataclass
class Outcome:
    req: object
    start: float               # perf_counter when it was due / sent
    end: float = 0.0
    sent: float = 0.0          # perf_counter when it was submitted
    ranked: list = field(default_factory=list)
                               # (index, makespan, scan_makespan, verified)
    error: str = ""

    @property
    def latency(self) -> float:
        return self.end - self.start


def freeze() -> None:
    """Move everything set-up made (JAX, the program's modules, warm
    caches) out of the garbage collector's sight, as a long-running
    server does after warming up: a full collection would otherwise walk
    millions of import-time objects and stall the event loop."""
    gc.collect()
    gc.freeze()


def program_st(st):
    from repro.core import ServiceTimes
    return ServiceTimes(**st)


def ranked(evals) -> list:
    return [(e.index, e.makespan, e.scan_makespan, e.verified)
            for e in evals]


class Base:
    def __init__(self, cell, gen, *, tracer, dag_dir, hooks):
        self.cell, self.gen, self.tracer = cell, gen, tracer
        self.dag_dir = dag_dir
        self.hooks = hooks             # window_open(), window_close()
        self.n_nodes = cell.config["n_nodes"]
        self.outcomes: list = []
        self.setup_done = 0.0
        self.window = (0.0, 0.0)

    def session(self):
        """The program's session on the cell's chips: one chip runs
        inline, more split each bucket's candidates over the first
        ``chips`` devices (`ShardedBackend`, the engine's own threshold
        deciding which buckets shard)."""
        from repro.core import InlineBackend, ShardedBackend, SweepSession
        chips = self.cell.chips
        backend = ShardedBackend(chips) if chips > 1 else InlineBackend()
        disk = self.cell.traffic.get("dag_disk_cache")
        return SweepSession(backend,
                            cache_dir=str(self.dag_dir) if disk else None,
                            tracer=self.tracer)

    def question(self, req):
        """The program's candidates and a workflow for each."""
        wfs = {}
        cands, prog = [], []
        for wf, lay in zip(req.workflows, req.layouts):
            if id(wf) not in wfs:
                wfs[id(wf)] = W.to_program(wf)
            cands.append(W.to_candidate(lay, self.n_nodes))
            prog.append(wfs[id(wf)])
        return cands, prog
