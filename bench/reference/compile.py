"""Plain reference of the workload compiler: a plain workflow (see
`bench.workflows`) on a healthy cluster layout -> micro-op DAG.

It follows the paper's queue model (arXiv:1302.4760 §2.3-2.4): one FIFO
single-server queue per host NIC (out and in), loopback, CPU, storage
service and the manager; a write asks the manager for an allocation,
stores each chunk down its replica chain and commits the chunk map; a
read asks the manager, fetches each chunk from replica ``j mod r`` and
joins them. It imports nothing of the system under test, so a fault in
the system's compiler shows as a different makespan.

The DAG is a dict of plain lists: ``res``, ``cls``, ``nbytes``, ``reqs``,
``extra``, ``nlat``, ``deps`` (MAXD predecessor ids, -1 = none) and
``n_resources``.
"""
from __future__ import annotations

MAXD = 4
CTRL_BYTES = 1024
# smallest normal f64: a runtime below it compiles as zero, as on the
# device, which flushes subnormals
TINY = 2.2250738585072014e-308

CLS_NONE, CLS_NET_REMOTE, CLS_NET_LOCAL, CLS_STORAGE, CLS_MANAGER, \
    CLS_CLIENT, CLS_CPU = range(7)


class _Manager:
    """Placement: a round-robin cursor over the storage nodes, with the
    per-file overrides local / collocate / broadcast."""

    def __init__(self, lay):
        self.lay = lay
        self.cursor = 0
        self.groups = {}
        self.files = {}

    def _stripe(self, width):
        s = self.lay["storage_hosts"]
        start = self.cursor % len(s)
        self.cursor += 1
        return [s[(start + i) % len(s)] for i in range(min(width, len(s)))]

    def _chain(self, primary, r):
        s = self.lay["storage_hosts"]
        i = s.index(primary)
        return [s[(i + k) % len(s)] for k in range(min(r, len(s)))]

    def place(self, name, size, writer, attr):
        lay = self.lay
        policy = (attr or {}).get("placement") or lay["placement"]
        repl = (attr or {}).get("replication") or lay["replication"]
        n_chunks = -(-size // lay["chunk_size"])
        if policy == "local" and writer in lay["storage_hosts"]:
            targets = [writer] * n_chunks
        elif policy == "collocate":
            group = (attr or {}).get("group") or name
            if group not in self.groups:
                self.groups[group] = self._stripe(1)[0]
            targets = [self.groups[group]] * n_chunks
        else:
            stripe = self._stripe(min(lay["stripe_width"],
                                      len(lay["storage_hosts"])))
            targets = [stripe[j % len(stripe)] for j in range(n_chunks)]
        loc = {"size": size, "chunk": lay["chunk_size"],
               "chunks": [self._chain(t, repl) for t in targets]}
        self.files[name] = loc
        return loc


def _chunk_bytes(loc, j):
    n = len(loc["chunks"])
    last = loc["size"] - (n - 1) * loc["chunk"]
    return loc["chunk"] if j < n - 1 else max(last, 0)


def _single_host(loc):
    hosts = {c[0] for c in loc["chunks"]}
    return hosts.pop() if len(hosts) == 1 else None


class _Dag:
    def __init__(self, lay):
        self.H = lay["n_hosts"]
        self.sidx = {h: i for i, h in enumerate(lay["storage_hosts"])}
        self.S = len(lay["storage_hosts"])
        self.res, self.cls, self.nbytes, self.reqs = [], [], [], []
        self.extra, self.nlat, self.deps = [], [], []

    def r_out(self, h): return 1 + h
    def r_in(self, h): return 1 + self.H + h
    def r_loop(self, h): return 1 + 2 * self.H + h
    def r_cpu(self, h): return 1 + 3 * self.H + h
    def r_store(self, h): return 1 + 4 * self.H + self.sidx[h]

    @property
    def r_manager(self): return 1 + 4 * self.H + self.S

    def op(self, res, cls, deps, *, nbytes=0.0, reqs=0.0, extra=0.0,
           nlat=0.0):
        deps = [d for d in deps if d >= 0]
        if len(deps) > MAXD:
            deps = [self.barrier(deps)]
        self.res.append(res)
        self.cls.append(cls)
        self.nbytes.append(float(nbytes))
        self.reqs.append(float(reqs))
        self.extra.append(float(extra))
        self.nlat.append(float(nlat))
        self.deps.append(deps + [-1] * (MAXD - len(deps)))
        return len(self.res) - 1

    def barrier(self, deps):
        deps = list(deps) or [-1]
        while len(deps) > MAXD:
            nxt = []
            for k in range(0, len(deps), MAXD):
                grp = deps[k:k + MAXD]
                nxt.append(self.op(0, CLS_NONE, grp) if len(grp) > 1
                           else grp[0])
            deps = nxt
        return self.op(0, CLS_NONE, deps)

    def hop(self, src, dst, nbytes, deps):
        if src == dst:
            return self.op(self.r_loop(src), CLS_NET_LOCAL, deps,
                           nbytes=nbytes, nlat=1.0)
        a = self.op(self.r_out(src), CLS_NET_REMOTE, deps, nbytes=nbytes)
        return self.op(self.r_in(dst), CLS_NET_REMOTE, [a], nbytes=nbytes,
                       nlat=1.0)

    def write(self, m, host, loc, deps):
        a = self.hop(host, m, CTRL_BYTES, deps)
        b = self.op(self.r_manager, CLS_MANAGER, [a], reqs=1.0)
        reply = self.hop(m, host, CTRL_BYTES, [b])
        done = []
        for j, chain in enumerate(loc["chunks"]):
            cb = _chunk_bytes(loc, j)
            d = self.hop(host, chain[0], cb, [reply])
            d = self.op(self.r_store(chain[0]), CLS_STORAGE, [d], nbytes=cb,
                        reqs=1.0)
            for prev, nxt in zip(chain, chain[1:]):
                d = self.hop(prev, nxt, cb, [d])
                d = self.op(self.r_store(nxt), CLS_STORAGE, [d], nbytes=cb,
                            reqs=1.0)
            done.append(d)
        allc = self.barrier(done)
        c = self.hop(host, m, CTRL_BYTES, [allc])
        d = self.op(self.r_manager, CLS_MANAGER, [c], reqs=1.0)
        return self.hop(m, host, CTRL_BYTES, [d])

    def read(self, m, host, loc, deps):
        a = self.hop(host, m, CTRL_BYTES, deps)
        b = self.op(self.r_manager, CLS_MANAGER, [a], reqs=1.0)
        reply = self.hop(m, host, CTRL_BYTES, [b])
        done = []
        for j, chain in enumerate(loc["chunks"]):
            cb = _chunk_bytes(loc, j)
            src = chain[j % len(chain)]
            d = self.hop(host, src, CTRL_BYTES, [reply])
            d = self.op(self.r_store(src), CLS_STORAGE, [d], nbytes=cb,
                        reqs=1.0)
            done.append(self.hop(src, host, cb, [d]))
        return self.barrier(done)


def compile_dag(wf, lay, *, locality_aware=True):
    """The micro-op DAG of a plain workflow on a plain layout. Tasks are
    listed producers first; a task without a pinned client goes to the
    node holding all its inputs (locality-aware), else the least loaded
    client."""
    mgr = _Manager(lay)
    dag = _Dag(lay)
    m = lay["manager_host"]
    for name, size, attr in wf["preloaded"]:
        mgr.place(name, size, m, attr)
    written = {name: -1 for name, _, _ in wf["preloaded"]}
    last_on = {}
    load = [0] * len(lay["client_hosts"])
    client_of = {h: i for i, h in enumerate(lay["client_hosts"])}
    for t in wf["tasks"]:
        c = t["client"]
        if c is None:
            if locality_aware and t["inputs"]:
                hosts = set()
                for f in t["inputs"]:
                    loc = mgr.files.get(f)
                    h = _single_host(loc) if loc is not None else None
                    if h is None:
                        hosts = set()
                        break
                    hosts.add(h)
                if len(hosts) == 1:
                    c = client_of.get(hosts.pop())
            if c is None:
                c = min(range(len(load)), key=lambda k: (load[k], k))
        load[c] += 1
        host = lay["client_hosts"][c]
        start_deps = [written[f] for f in t["inputs"]]
        if c in last_on:
            start_deps.append(last_on[c])
        start = dag.barrier(start_deps)
        reads = [dag.read(m, host, mgr.files[f], [start]) for f in t["inputs"]]
        ready = dag.barrier(reads) if reads else start
        runtime = t["runtime"] if abs(t["runtime"]) >= TINY else 0.0
        comp = dag.op(dag.r_cpu(host), CLS_CPU, [ready], extra=runtime)
        ends = []
        for name, size in t["outputs"]:
            loc = mgr.place(name, size, host, t["attrs"].get(name))
            w = dag.write(m, host, loc, [comp])
            written[name] = w
            ends.append(w)
        last_on[c] = dag.barrier(ends + [comp])
    return {"res": dag.res, "cls": dag.cls, "nbytes": dag.nbytes,
            "reqs": dag.reqs, "extra": dag.extra, "nlat": dag.nlat,
            "deps": dag.deps, "n_resources": 1 + 4 * dag.H + dag.S + 1}
