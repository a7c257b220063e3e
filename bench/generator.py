"""The one traffic generator: a configuration and a mix's parameters in,
a seeded stream of requests out, each with the time it is due.

A request is one operator question: a workflow per candidate (the
candidate's app nodes set the workflow's width, its family's file
``bench/families/<family>.py`` builds it), the candidate grid, the
service times to judge it under, and ``verify_top_k``. A mix
(``bench/traffic/<mix>.json``) is these parameters:

    driver        the entry that serves it, ``bench/drivers/<driver>.py``
    question      what one request spans: "grid", every candidate of the
                  configuration's grid (one `explore`); "split", one
                  workflow on one app/storage split over that split's
                  candidates (one `AdvisorRequest`)
    verify_top_k  candidates per answer the exact simulation verifies
    arrivals      {"process": "closed"}: each request is sent when the
                  last answer came; {"process": "even" | "poisson",
                  "rate_per_s": r, "burst": b}: an open loop of bursts of
                  b requests (default 1) at r / b bursts per second,
                  evenly spaced or with exponential gaps
    population    0 or absent: every request is a new question. P > 0:
                  requests recur, drawn from P questions made once, the
                  k-th most frequent with weight 1 / k ** zipf_s
    zipf_s        the skew of those draws (default 1.0)
    size_jitter_kb    a new question takes 1 to this many KB off every
                  file, drawn without repeats per class, so no two are
                  alike and, with base sizes of whole chunks, no chunk
                  count changes; a new question of the "grid" kind then
                  compiles new DAGs
    what_if       service times drawn per new question: a profile and
                  log-uniform factors on the network and storage rates
    check         what `check` compares, and its limits
    dag_disk_cache    keep compiled DAGs on disk in the checkout

Every seed gets the same work in another order (see `Generator`):
the same arrivals, poisson gaps included, and the same multiset of
question classes (workflow x split) or of recurring questions.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass

import numpy as np

from . import spec as S
from . import workflows as W

KB = W.KB


@dataclass
class Request:
    workflows: list            # plain workflow per candidate
    layouts: list              # plain layout per candidate
    st: dict                   # service times (reference form)
    verify_top_k: int


def service_times(profile: dict) -> dict:
    """A configuration's profile (rates) -> seconds per byte / request."""
    return {"net_remote": 1.0 / profile["net_remote_Bps"],
            "net_local": 1.0 / profile["net_local_Bps"],
            "net_latency": profile["net_latency_s"],
            "storage": 1.0 / profile["storage_Bps"],
            "manager": profile["manager_s"],
            "client": profile["client_s"],
            "storage_req": profile["storage_req_s"]}


def partitions(cfg: dict) -> list:
    if cfg["partitions"] == "all":
        total = cfg["n_nodes"]
        return [(a, total - 1 - a) for a in range(1, total - 1)]
    return [tuple(p) for p in cfg["partitions"]]


def layouts_for(cfg: dict, split) -> list:
    """The grid's candidates on one split, in the program's grid order
    (chunk size, then stripe width, then replication); stripe widths
    and replication factors the split cannot hold are skipped."""
    n_app, n_st = split
    out = []
    for ck, sw, r in itertools.product(cfg["chunk_kb"], cfg["stripe_widths"],
                                       cfg["replications"]):
        if r > n_st or sw > n_st:
            continue
        out.append(W.layout(n_app, n_st, chunk_size=ck * KB,
                            stripe_width=sw, replication=r))
    return out


def build_workflow(cfg: dict, spec: dict, n_app: int, cut: int = 0) -> dict:
    """One workflow of the configuration, ``n_app`` wide, every file
    ``cut`` bytes smaller than the configuration states: the family the
    spec names, ``bench/families/<family>.py``."""
    return S.load_family(spec["family"])(cfg, spec, n_app, cut)


def _classes(cfg: dict, mix: dict) -> list:
    """Question classes: (workflow spec, splits it spans)."""
    splits = partitions(cfg)
    if mix["question"] == "grid":
        return [(spec, splits) for spec in cfg["workflows"]]
    if mix["question"] == "split":
        return [(spec, [s]) for spec in cfg["workflows"] for s in splits]
    raise ValueError(f"unknown question kind {mix['question']!r}")


def exp_gaps(n: int, total: float) -> np.ndarray:
    """``n`` exponential gaps at evenly spaced quantiles, scaled to sum
    to ``total``: the same set for every seed."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (total / g.sum())


def apportion(weights, n: int) -> list:
    """``n`` draws shared out by weight, largest remainders first."""
    w = np.asarray(weights, dtype=float)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


class Generator:
    """``next()`` gives ``(due, request)``: ``due`` in seconds after the
    window opens (``math.inf`` once the window's arrivals are spent), or
    None in a closed loop. ``warmup()`` gives new questions of every
    class, never a window's.

    An open loop's window of ``seconds`` holds the same requests for
    every seed: its N arrivals draw the question classes (or the
    population's questions) in one fixed multiset, N shared out by
    weight, in seeded order. A closed loop draws in blocks of that kind
    (one per class, or 4 per population member)."""

    def __init__(self, cfg: dict, mix: dict, seed: int, seconds: float):
        self.cfg, self.mix = cfg, mix
        self.rng = np.random.default_rng(seed)
        self.classes = _classes(cfg, mix)
        self.profiles = {k: service_times(v)
                         for k, v in cfg["service_times"].items()}
        jit = mix.get("size_jitter_kb") or 0
        # per class, the cuts not yet used (1..jit KB, seeded order)
        self._cuts = [list(self.rng.permutation(jit) + 1) if jit else []
                      for _ in self.classes]
        self.dues = self._dues(mix["arrivals"], seconds)
        pop = mix.get("population") or 0
        self.population = [self._fresh(k % len(self.classes))
                           for k in range(pop)]
        self.weights = (1.0 / np.arange(1, pop + 1) ** mix.get("zipf_s", 1.0)
                        if pop else np.ones(len(self.classes)))
        self._plan = (self._block(len(self.dues))
                      if self.dues is not None else [])
        self._n = 0

    def _dues(self, arr: dict, seconds: float):
        """Due times of the window's requests; None in a closed loop."""
        process = arr["process"]
        if process == "closed":
            return None
        burst = int(arr.get("burst", 1))
        gap = burst / arr["rate_per_s"]
        if process == "even":
            starts = np.arange(math.ceil(seconds / gap)) * gap
        elif process == "poisson":
            m = max(1, round(seconds / gap))
            starts = np.concatenate(
                [[0.0], np.cumsum(self.rng.permutation(
                    exp_gaps(m, seconds)))[:-1]])
        else:
            raise ValueError(f"unknown arrival process {process!r}")
        return [float(t) for t in starts for _ in range(burst)]

    def _block(self, n: int) -> list:
        counts = apportion(self.weights, n)
        return list(self.rng.permutation(
            [i for i, c in enumerate(counts) for _ in range(c)]))

    def _st(self) -> dict:
        w = self.mix.get("what_if")
        if not w:
            return dict(self.profiles[self.cfg["service_profile"]])
        name = w["profiles"][int(self.rng.integers(len(w["profiles"])))]
        st = dict(self.profiles[name])
        lo, hi = w["net_scale"]
        fn = math.exp(self.rng.uniform(math.log(lo), math.log(hi)))
        lo, hi = w["storage_scale"]
        fs = math.exp(self.rng.uniform(math.log(lo), math.log(hi)))
        # a rate scaled by f takes 1/f the seconds per byte
        st["net_remote"] /= fn
        st["net_local"] /= fn
        st["storage"] /= fs
        return st

    def _fresh(self, k: int) -> Request:
        """A question of class ``k`` that no earlier one repeats."""
        spec, splits = self.classes[k]
        cut = int(self._cuts[k].pop()) * KB if self._cuts[k] else 0
        wfs, lays = [], []
        for split in splits:
            wf = build_workflow(self.cfg, spec, split[0], cut)
            for lay in layouts_for(self.cfg, split):
                wfs.append(wf)
                lays.append(lay)
        return Request(workflows=wfs, layouts=lays, st=self._st(),
                       verify_top_k=self.mix["verify_top_k"])

    def next(self) -> tuple:
        if not self._plan:
            self._plan = self._block(len(self.weights)
                                     * (4 if self.population else 1))
        i = int(self._plan.pop())
        req = self.population[i] if self.population else self._fresh(i)
        due = None
        if self.dues is not None:
            due = self.dues[self._n] if self._n < len(self.dues) else math.inf
        self._n += 1
        return due, req

    def warmup(self) -> list:
        """One new question per class: the shapes the window uses."""
        return [self._fresh(k) for k in range(len(self.classes))]
