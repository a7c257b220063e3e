"""The benchmark's own workflow builders and candidate layouts.

A workflow is plain data here: a dict with ``tasks`` (each with ``tid``,
``inputs``, ``outputs`` as ``[name, bytes]`` pairs, ``runtime``,
``client``, ``stage`` and per-file ``attrs``) and ``preloaded`` (``[name,
bytes, attr]`` triples in placement order). The reference simulators
read this form directly; `to_program` turns it into the program's
`Workflow` for the system under test. The builders follow the paper's
workloads (arXiv:1302.4760 §3): BLAST (§3.2, Fig. 7), reduce (Fig. 5),
broadcast (Fig. 6) and the Fig. 1 stripe-width mix.
"""
from __future__ import annotations

MB = 1 << 20
KB = 1 << 10


def _task(tid, inputs, outputs, *, runtime=0.0, client=None, stage="",
          attrs=None):
    return {"tid": tid, "inputs": list(inputs),
            "outputs": [[n, int(s)] for n, s in outputs],
            "runtime": float(runtime), "client": client, "stage": stage,
            "attrs": dict(attrs or {})}


def blast(n_app, *, n_queries, db_bytes, per_query_s, query_bytes,
          out_bytes):
    """Every app node reads the shared database and its own query file,
    searches its share of the queries and writes its results."""
    pre = [["db", int(db_bytes), None]]
    per_node = [n_queries // n_app + (1 if k < n_queries % n_app else 0)
                for k in range(n_app)]
    tasks = []
    for k in range(n_app):
        pre.append([f"queries{k}", int(query_bytes), None])
        tasks.append(_task(k, ("db", f"queries{k}"),
                           ((f"result{k}", out_bytes),),
                           runtime=per_node[k] * per_query_s, client=k,
                           stage="search"))
    return {"name": f"blast_{n_app}app", "tasks": tasks, "preloaded": pre}


def reduce_(n_workers, *, in_bytes, mid_bytes, out_bytes, wass):
    """n parallel producers, one consumer. WASS collocates the
    intermediate files on one node and keeps the result local."""
    coll = {"placement": "collocate", "group": "reduce"} if wass else None
    local = {"placement": "local"} if wass else None
    pre = [[f"in{k}", int(in_bytes), None] for k in range(n_workers)]
    tasks = [_task(k, (f"in{k}",), ((f"mid{k}", mid_bytes),), client=k,
                   stage="map", attrs={f"mid{k}": coll} if coll else None)
             for k in range(n_workers)]
    tasks.append(_task(n_workers, [f"mid{k}" for k in range(n_workers)],
                       (("reduced", out_bytes),), stage="reduce",
                       attrs={"reduced": local} if local else None))
    return {"name": f"reduce_{'wass' if wass else 'dss'}", "tasks": tasks,
            "preloaded": pre}


def broadcast(n_consumers, *, file_bytes, out_bytes, replication):
    """One producer, n consumers of its file; WASS replicates the hot
    file eagerly."""
    attr = {"placement": "broadcast", "replication": replication} \
        if replication > 1 else None
    tasks = [_task(0, ("in0",), (("hot", file_bytes),), client=0,
                   stage="produce", attrs={"hot": attr} if attr else None)]
    for k in range(n_consumers):
        tasks.append(_task(1 + k, ("hot",), ((f"out{k}", out_bytes),),
                           client=k, stage="consume"))
    return {"name": f"broadcast_r{replication}", "tasks": tasks,
            "preloaded": [["in0", int(file_bytes), None]]}


def stripe(n_clients, *, file_bytes, n_hot, out_bytes):
    """Fig. 1: a few producers write shared files that every client
    then reads."""
    pre, tasks = [], []
    for h in range(n_hot):
        pre.append([f"in{h}", int(file_bytes), None])
        tasks.append(_task(h, (f"in{h}",), ((f"hot{h}", file_bytes),),
                           client=h, stage="write"))
    for k in range(n_clients):
        tasks.append(_task(n_hot + k, [f"hot{h}" for h in range(n_hot)],
                           ((f"out{k}", out_bytes),), client=k,
                           stage="read"))
    return {"name": "stripe_sweep", "tasks": tasks, "preloaded": pre}


def layout(n_app, n_storage, *, chunk_size, stripe_width=0, replication=1):
    """Scenario I's partitioned cluster as plain data: manager on host
    0, storage on hosts 1..S, clients after them. Stripe width 0 means
    every storage node."""
    n_hosts = 1 + n_storage + n_app
    return {"n_hosts": n_hosts,
            "storage_hosts": list(range(1, 1 + n_storage)),
            "client_hosts": list(range(1 + n_storage, n_hosts)),
            "manager_host": 0,
            "stripe_width": stripe_width or n_storage,
            "replication": replication, "chunk_size": int(chunk_size),
            "placement": "round_robin"}


def to_program(wf):
    """The program's `Workflow` for one plain workflow."""
    from repro.core.types import FileAttr, Placement, Task, Workflow

    def attr(a):
        if a is None:
            return None
        return FileAttr(placement=Placement(a["placement"]),
                        replication=a.get("replication"),
                        collocate_group=a.get("group"))

    tasks = [Task(tid=t["tid"], inputs=tuple(t["inputs"]),
                  outputs=tuple((n, s) for n, s in t["outputs"]),
                  runtime=t["runtime"], client=t["client"], stage=t["stage"],
                  file_attrs={f: attr(a) for f, a in t["attrs"].items()})
             for t in wf["tasks"]]
    return Workflow(tasks=tasks, name=wf["name"],
                    preloaded={n: (s, attr(a)) for n, s, a in wf["preloaded"]})


def to_candidate(lay, n_nodes):
    """The program's `Candidate` for one plain layout."""
    from repro.core import Candidate
    return Candidate(n_nodes=n_nodes, n_app=len(lay["client_hosts"]),
                     n_storage=len(lay["storage_hosts"]),
                     chunk_size=lay["chunk_size"],
                     stripe_width=lay["stripe_width"],
                     replication=lay["replication"])
