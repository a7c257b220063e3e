"""Plain reference simulators over a reference DAG (`compile.compile_dag`).

* `exact_makespan` — the discrete-event simulation of the queue model:
  an op is ready once its predecessors completed (plus network lag);
  ready ops are served in ready-time order, ties by op id, each through
  its resource's FIFO queue.
* `scan_makespan` — scan-mode semantics: ops are served once, in
  estimated-start order (a contention-free forward pass, stable-sorted),
  each starting once its dependencies completed and its resource is
  free. What the system's scan mode computes on the device.

Service times are a dict with the seven rates of `bench.workflows`'
configurations (seconds per byte or per request).
"""
from __future__ import annotations

import heapq

from .compile import (CLS_CLIENT, CLS_MANAGER, CLS_NET_LOCAL,
                      CLS_NET_REMOTE, CLS_STORAGE)


def durations(dag, st):
    """Per-op service seconds: bytes x byte rate + requests x request
    rate + fixed compute, by service class."""
    brate = [0.0] * 7
    rrate = [0.0] * 7
    brate[CLS_NET_REMOTE] = st["net_remote"]
    brate[CLS_NET_LOCAL] = st["net_local"]
    brate[CLS_STORAGE] = st["storage"]
    rrate[CLS_MANAGER] = st["manager"]
    rrate[CLS_CLIENT] = st["client"]
    rrate[CLS_STORAGE] = st["storage_req"]
    return [nb * brate[c] + rq * rrate[c] + ex
            for nb, rq, ex, c in zip(dag["nbytes"], dag["reqs"],
                                     dag["extra"], dag["cls"])]


def lags(dag, st):
    return [x * st["net_latency"] for x in dag["nlat"]]


def estimated_start_order(dag, st):
    """Ops in contention-free estimated-start order, ties by op id."""
    dur = [d + g for d, g in zip(durations(dag, st), lags(dag, st))]
    n = len(dur)
    start = [0.0] * n
    end = [0.0] * n
    for i, deps in enumerate(dag["deps"]):
        s = 0.0
        for d in deps:
            if d >= 0 and end[d] > s:
                s = end[d]
        start[i] = s
        end[i] = s + dur[i]
    return sorted(range(n), key=start.__getitem__)


def scan_makespan(dag, st):
    dur = durations(dag, st)
    lag = lags(dag, st)
    avail = [0.0] * dag["n_resources"]
    end = [0.0] * len(dur)
    served = [False] * len(dur)
    makespan = 0.0
    for i in estimated_start_order(dag, st):
        ready = 0.0
        for d in dag["deps"][i]:
            # a dependency not yet served in scan order reads as 0.0
            if d >= 0 and served[d] and end[d] > ready:
                ready = end[d]
        r = dag["res"][i]
        start = ready if ready > avail[r] else avail[r]
        fin = start + dur[i]
        avail[r] = fin
        end[i] = fin + lag[i]
        served[i] = True
        if fin > makespan:
            makespan = fin
    return makespan


def exact_makespan(dag, st):
    dur = durations(dag, st)
    lag = lags(dag, st)
    n = len(dur)
    indeg = [0] * n
    children = [[] for _ in range(n)]
    for i, deps in enumerate(dag["deps"]):
        for d in deps:
            if d >= 0:
                indeg[i] += 1
                children[d].append(i)
    end = [0.0] * n
    ready = [0.0] * n
    avail = [0.0] * dag["n_resources"]
    heap = [(0.0, i) for i in range(n) if indeg[i] == 0]
    heapq.heapify(heap)
    done = 0
    makespan = 0.0
    while heap:
        t, i = heapq.heappop(heap)
        r = dag["res"][i]
        start = t if t > avail[r] else avail[r]
        fin = start + dur[i]
        avail[r] = fin
        end[i] = fin + lag[i]
        if fin > makespan:
            makespan = fin
        done += 1
        for c in children[i]:
            if end[i] > ready[c]:
                ready[c] = end[i]
            indeg[c] -= 1
            if indeg[c] == 0:
                heapq.heappush(heap, (ready[c], c))
    if done != n:
        raise ValueError(f"cyclic or dangling DAG: served {done} of {n} ops")
    return makespan
