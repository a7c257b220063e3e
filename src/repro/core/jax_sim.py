"""Vectorized JAX implementation of the queue-based storage model.

This is the TPU-native adaptation of the paper's Java discrete-event
simulator (DESIGN.md §3): the compiled micro-op DAG has static shape, so
the whole simulation becomes a `lax.scan` (fast mode) or `lax.while_loop`
(exact mode) over fixed arrays — and therefore `jit`-compilable and
`vmap`-able over *batches of configurations and service times*. A full
configuration-space sweep (the paper's Figures 8–9 grids) is one XLA
program.

Modes
-----
* ``exact=True``  — bit-exact DES: repeatedly serve the unscheduled op
  with minimal ready time (ties by op id), identical semantics to
  `ref_sim.simulate`. O(N^2) work, used for validation and small runs.
* ``exact=False`` — FIFO arrival order approximated by emission order
  (one `lax.scan` pass, O(N·MAXD)). Exact whenever emission order agrees
  with ready order — true for the symmetric fan-out/fan-in patterns of
  workflow benchmarks — and within a few percent otherwise (tested).

Service times enter as a traced 7-vector, so "what-if" hardware sweeps
(§2.1: e.g. SSDs) re-use one compiled program.

Ops are pre-permuted into *estimated-start order* (contention-free
forward pass at compile time): the fast mode serves each FIFO resource in
scan order, so scan order must approximate arrival order — emission order
does not (stage-2 ops of an early pipeline are emitted before stage-0 ops
of a later one), estimated-start order does.

Simulations run in x64 (times in seconds need more than f32's 7 digits
to reproduce the oracle's FIFO tie-breaking); the model/training code in
the rest of the framework stays in the default f32/bf16 world.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.sweep_scan.ref import scan_serve
from .compile import (CLS_CLIENT, CLS_MANAGER, CLS_NET_LOCAL, CLS_NET_REMOTE,
                      CLS_STORAGE, MAXD, N_CLS, MicroOps)
from .faults import DEAD_TIME
from .types import RunReport, ServiceTimes
from .x64 import enable_x64

# service-time vector layout
(ST_NET_REMOTE, ST_NET_LOCAL, ST_NET_LATENCY, ST_STORAGE, ST_MANAGER,
 ST_CLIENT, ST_STORAGE_REQ) = range(7)


def st_to_vec(st: ServiceTimes) -> np.ndarray:
    return np.array([st.net_remote, st.net_local, st.net_latency,
                     st.storage, st.manager, st.client, st.storage_req],
                    dtype=np.float64)


def _rates(st_vec: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    brate = jnp.zeros(N_CLS, st_vec.dtype)
    brate = brate.at[CLS_NET_REMOTE].set(st_vec[ST_NET_REMOTE])
    brate = brate.at[CLS_NET_LOCAL].set(st_vec[ST_NET_LOCAL])
    brate = brate.at[CLS_STORAGE].set(st_vec[ST_STORAGE])
    rrate = jnp.zeros(N_CLS, st_vec.dtype)
    rrate = rrate.at[CLS_MANAGER].set(st_vec[ST_MANAGER])
    rrate = rrate.at[CLS_CLIENT].set(st_vec[ST_CLIENT])
    rrate = rrate.at[CLS_STORAGE].set(st_vec[ST_STORAGE_REQ])
    return brate, rrate


@jax.tree_util.register_pytree_node_class
@dataclass
class OpArrays:
    """Device-side compiled DAG (possibly padded for batching)."""

    res: jnp.ndarray      # i32[N]
    cls: jnp.ndarray      # i32[N]
    nbytes: jnp.ndarray   # f64[N]
    reqs: jnp.ndarray     # f64[N]
    extra: jnp.ndarray    # f64[N]
    nlat: jnp.ndarray     # f64[N]
    deps: jnp.ndarray     # i32[N, MAXD]

    def tree_flatten(self):
        return ((self.res, self.cls, self.nbytes, self.reqs, self.extra,
                 self.nlat, self.deps), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @classmethod
    def from_micro_ops(cls, ops: MicroOps, pad_to: int | None = None,
                       perm: np.ndarray | None = None) -> "OpArrays":
        n = ops.n_ops
        m = pad_to or n
        assert m >= n

        def prep(a, fill=0):
            a = a[perm] if perm is not None else a
            out = np.full((m,) + a.shape[1:], fill, dtype=a.dtype)
            out[:n] = a
            return out

        deps = ops.deps
        if perm is not None:
            inv = np.empty(n, dtype=np.int32)
            inv[perm] = np.arange(n, dtype=np.int32)
            deps = np.where(deps >= 0, inv[deps], -1).astype(np.int32)

        with enable_x64():
            return cls(res=jnp.asarray(prep(ops.res)),
                       cls=jnp.asarray(prep(ops.cls.astype(np.int32))),
                       nbytes=jnp.asarray(prep(ops.nbytes)),
                       reqs=jnp.asarray(prep(ops.reqs)),
                       extra=jnp.asarray(prep(ops.extra)),
                       nlat=jnp.asarray(prep(ops.nlat)),
                       deps=jnp.asarray(prep(deps, fill=-1)))


@jax.tree_util.register_pytree_node_class
@dataclass
class FaultArrays:
    """Device-side fault scenario, shaped to ride the same `jit(vmap)`
    as `OpArrays` (docs/faults.md): a per-resource service-time
    multiplier and a per-op death mask. `None` stands in for the healthy
    case everywhere — the healthy jaxpr never materializes these arrays,
    so the no-fault path stays byte-identical to the pre-fault build."""

    res_mult: jnp.ndarray   # f64[R] service-time multiplier per resource
    dead: jnp.ndarray       # f64[N] 1.0 = unservable op (costs DEAD_TIME)

    def tree_flatten(self):
        return ((self.res_mult, self.dead), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)

    @classmethod
    def from_micro_ops(cls, ops: MicroOps, n_resources: int | None = None,
                       pad_to: int | None = None,
                       perm: np.ndarray | None = None) -> "FaultArrays":
        """Padded/permuted fault arrays matching an `OpArrays` built with
        the same ``pad_to``/``perm``. Padded resources multiply by 1 and
        padded ops are alive, so padding stays inert."""
        R = n_resources or ops.n_resources
        n, m = ops.n_ops, pad_to or ops.n_ops
        rm = np.ones(R, dtype=np.float64)
        if ops.res_mult is not None:
            rm[:ops.n_resources] = ops.res_mult
        dd = np.zeros(m, dtype=np.float64)
        if ops.dead is not None:
            dd[:n] = ops.dead[perm] if perm is not None else ops.dead
        with enable_x64():
            return cls(res_mult=jnp.asarray(rm), dead=jnp.asarray(dd))

    @classmethod
    def neutral(cls, n_ops: int, n_resources: int) -> "FaultArrays":
        """All-ones / all-zeros arrays for healthy rows batched alongside
        faulted ones: multiplying by 1.0 and adding 0.0 are exact in
        f64, so a healthy row simulated through the faulted executable
        is element-wise identical to the healthy executable's result
        (counter-asserted in tests/test_faults.py). The dtype is
        *canonicalized*, never a bare float64 literal: with the x64 shim
        disabled (``REPRO_SIM_X64=0``) a literal would warn and silently
        mix f32 rows into f64 batches — here the arrays always match
        whatever dtype `OpArrays.from_micro_ops` produced in the same
        mode."""
        with enable_x64():
            dt = jax.dtypes.canonicalize_dtype(np.float64)
            return cls(res_mult=jnp.ones(n_resources, dt),
                       dead=jnp.zeros(n_ops, dt))


def faulted(ops: MicroOps) -> bool:
    """Does this compiled DAG carry fault state the simulator must apply?"""
    return ops.res_mult is not None or ops.dead is not None


# `scan_order` takes the level-synchronous pass for DAGs of at least this
# many ops. Below it the per-op loop costs less than building the levels
# (on CPU the two break even near 4k ops for BLAST's 32-36 levels).
LEVEL_MIN_OPS = 4096
# A DAG whose levels average fewer ops than this is left to the loop: the
# pass pays a few NumPy calls (tens of µs) a level, the loop well under a
# µs an op, so a deep, narrow DAG such as a chain is cheaper op by op.
LEVEL_MIN_WIDTH = 64


@dataclass(frozen=True)
class DagLevels:
    """One DAG's topological level partition, the part of `scan_order`
    that no service time changes. Ops are relabelled in level order:
    ``order[j]`` is the op at position j, level k holds positions
    ``bounds[k]:bounds[k+1]``, and ``deps[:, j]`` are that op's deps as
    positions, -1 mapped to the sentinel position N whose end is 0.
    ``order`` None (`SEQUENTIAL`): the per-op loop orders this DAG."""

    order: np.ndarray | None = None     # intp[N]
    deps: np.ndarray | None = None      # intp[MAXD, N]
    bounds: tuple = ()

    @property
    def vectorized(self) -> bool:
        return self.order is not None


SEQUENTIAL = DagLevels()


def dag_levels(ops: MicroOps) -> DagLevels:
    """The DAG's level partition, built frontier by frontier (Kahn) in
    O(N + E) work and one round of NumPy calls per level; `SEQUENTIAL`
    for a DAG under `LEVEL_MIN_OPS` ops, for one whose levels would
    average fewer than `LEVEL_MIN_WIDTH` ops (the build stops there), and
    for one with a dep at or after its own op, which the compiler never
    emits: the loop keeps its semantics there (such a dep reads 0)."""
    n = ops.n_ops
    deps = ops.deps
    if n == 0 or n < LEVEL_MIN_OPS or (deps >= np.arange(n)[:, None]).any():
        return SEQUENTIAL
    valid = deps >= 0
    parent = deps[valid]
    child = np.nonzero(valid)[0]
    kids = child[np.argsort(parent, kind="stable")]   # CSR children
    first = np.zeros(n + 1, np.intp)
    np.cumsum(np.bincount(parent, minlength=n), out=first[1:])
    indeg = valid.sum(axis=1)
    max_levels = n // LEVEL_MIN_WIDTH if LEVEL_MIN_WIDTH else n
    levels = []
    front = np.flatnonzero(indeg == 0)
    while front.size:
        if len(levels) == max_levels:
            return SEQUENTIAL
        levels.append(front)
        lo = first[front]
        cnt = first[front + 1] - lo
        pos = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        ch, hits = np.unique(kids[pos], return_counts=True)
        indeg[ch] -= hits
        front = ch[indeg[ch] == 0]
    order = np.concatenate(levels)
    assert order.size == n
    at = np.empty(n + 1, np.intp)
    at[order] = np.arange(n)
    at[n] = n
    dd = np.where(deps >= 0, deps, n)[order]
    return DagLevels(order=order, deps=np.ascontiguousarray(at[dd].T),
                     bounds=tuple(np.cumsum([0] + [f.size for f in levels])
                                  .tolist()))


def _starts_by_loop(deps: np.ndarray, dur: np.ndarray) -> np.ndarray:
    """Estimated starts one op at a time, in emission order."""
    dur = dur.tolist()
    ends = [0.0] * len(dur)
    start = [0.0] * len(dur)
    for i, row in enumerate(deps.tolist()):
        s = 0.0
        for d in row:
            if d >= 0 and ends[d] > s:
                s = ends[d]
        start[i] = s
        ends[i] = s + dur[i]
    return np.array(start)


def _starts_by_levels(dur: np.ndarray, lv: DagLevels) -> np.ndarray:
    """Estimated starts one level at a time: each op takes the same max
    over the same dep ends and the same single add as in the loop (whose
    max starts at 0: durations are non-negative, so every end is too)."""
    n = dur.shape[0]
    durp = dur[lv.order]
    ends = np.zeros(n + 1)          # position N: the sentinel
    startp = np.empty(n)
    for a, b in zip(lv.bounds[:-1], lv.bounds[1:]):
        s = ends.take(lv.deps[:, a:b]).max(axis=0)
        startp[a:b] = s
        np.add(s, durp[a:b], out=ends[a:b])
    start = np.empty(n)
    start[lv.order] = startp
    return start


def scan_order(ops: MicroOps, st_ref: ServiceTimes,
               levels: DagLevels | None = None) -> np.ndarray:
    """Permutation of ops into contention-free estimated-start order.

    One forward pass computes each op's earliest start ignoring queueing;
    a stable sort on (est_start, op id) then approximates the arrival
    order at every FIFO resource. Computed against a *reference*
    ServiceTimes — the simulated times stay fully parameterized, only the
    serving order is frozen (tested to stay within a few percent of the
    exact-order oracle; use exact=True when it must be bit-faithful).

    ``levels`` is the DAG's `dag_levels`, built here when not given (the
    sweep engine caches it per DAG). A vectorized partition runs the pass
    level by level, `SEQUENTIAL` op by op; both give the same starts to
    the bit, so the same permutation."""
    from .ref_sim import durations  # shared rate tables
    dur = durations(ops, st_ref) + ops.nlat * st_ref.net_latency
    if levels is None:
        levels = dag_levels(ops)
    start = (_starts_by_levels(dur, levels) if levels.vectorized
             else _starts_by_loop(ops.deps, dur))
    return np.argsort(start, kind="stable").astype(np.int32)


def _durations(a: OpArrays, st_vec: jnp.ndarray,
               f: FaultArrays | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    brate, rrate = _rates(st_vec)
    dur = a.nbytes * brate[a.cls] + a.reqs * rrate[a.cls] + a.extra
    if f is not None:
        # degraded/straggler resources serve slower; unservable ops cost
        # DEAD_TIME (finite — see faults.py — so exact-mode min-ready
        # ordering and f64 sums stay well-defined)
        dur = dur * f.res_mult[a.res] + f.dead * DEAD_TIME
    lag = a.nlat * st_vec[ST_NET_LATENCY]
    return dur, lag


# Refinement passes re-sort the serving order by the previous pass's ready
# times. Measured (see EXPERIMENTS.md §Perf, lesson L2): helps pure fan-out
# patterns (broadcast 10.2%->1.8% vs oracle) but *oscillates* for chained
# pipelines (7.6%->37%) — the iteration is not a contraction. Default is
# therefore 1 (host estimated-start order only); use exact=True when the
# schedule must be oracle-faithful, or the sweep->verify workflow in
# `search.py` (scan-mode shortlist, exact-mode confirmation).
SCAN_REFINE_PASSES = 1


def _scan_once(a: OpArrays, dur: jnp.ndarray, lag: jnp.ndarray,
               n_resources: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    # the FIFO serving recurrence itself lives in kernels/sweep_scan —
    # one implementation shared by this XLA path and the fused Pallas
    # kernel the sweep engine builds on (ops.sweep_scan)
    return scan_serve(a.res, dur, lag, a.deps, n_resources)


def _permute(a: OpArrays, order: jnp.ndarray) -> tuple[OpArrays, jnp.ndarray]:
    n = a.res.shape[0]
    inv = jnp.zeros(n, order.dtype).at[order].set(jnp.arange(n, dtype=order.dtype))
    deps = a.deps[order]
    deps = jnp.where(deps >= 0, inv[jnp.clip(deps, 0)], -1)
    return OpArrays(res=a.res[order], cls=a.cls[order], nbytes=a.nbytes[order],
                    reqs=a.reqs[order], extra=a.extra[order], nlat=a.nlat[order],
                    deps=deps), inv


def _sim_scan(a: OpArrays, st_vec: jnp.ndarray, n_resources: int,
              f: FaultArrays | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fast mode: serve each FIFO resource in scan order. The initial
    order (host-side `scan_order`) approximates arrival order; refinement
    passes re-sort by the *actual* start times of the previous pass,
    converging to a self-consistent FIFO schedule."""
    dur, lag = _durations(a, st_vec, f)
    makespan, end = _scan_once(a, dur, lag, n_resources)
    total_inv = None
    cur = a
    for _ in range(SCAN_REFINE_PASSES - 1):
        # DES serves in READY-time order: recompute each op's ready time
        # from the previous pass's completion times and re-sort.
        ready = jnp.max(jnp.where(cur.deps >= 0, end[cur.deps], 0.0), axis=1)
        order = jnp.argsort(ready, stable=True)
        cur, inv = _permute(cur, order)
        total_inv = inv if total_inv is None else inv[total_inv]
        # durations are per-op, so permuting them == recomputing from the
        # permuted arrays (and it keeps the fault mask aligned for free)
        dur, lag = dur[order], lag[order]
        makespan, end = _scan_once(cur, dur, lag, n_resources)
    if total_inv is not None:
        end = end[total_inv]
    return makespan, end


def _sim_exact(a: OpArrays, st_vec: jnp.ndarray, n_resources: int,
               f: FaultArrays | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact mode: global min-ready-time service order (== ref_sim)."""
    n = a.res.shape[0]
    dur, lag = _durations(a, st_vec, f)
    INF = jnp.asarray(jnp.finfo(dur.dtype).max, dur.dtype)

    def body(state):
        k, avail, end, done, makespan = state
        dep_end = jnp.where(a.deps >= 0, end[a.deps], 0.0)       # [N, MAXD]
        dep_done = jnp.where(a.deps >= 0, done[a.deps], True)
        frontier = jnp.all(dep_done, axis=1) & ~done
        ready = jnp.max(dep_end, axis=1)
        key = jnp.where(frontier, ready, INF)
        i = jnp.argmin(key)                                       # ties -> lowest id
        r = a.res[i]
        start = jnp.maximum(ready[i], avail[r])
        fin = start + dur[i]
        return (k + 1, avail.at[r].set(fin), end.at[i].set(fin + lag[i]),
                done.at[i].set(True), jnp.maximum(makespan, fin))

    state = (jnp.asarray(0), jnp.zeros(n_resources, dur.dtype),
             jnp.zeros(n, dur.dtype), jnp.zeros(n, bool), jnp.asarray(0.0, dur.dtype))
    state = jax.lax.while_loop(lambda s: s[0] < n, body, state)
    _, _, end, _, makespan = state
    return makespan, end


@functools.partial(jax.jit, static_argnames=("n_resources", "exact"))
def simulate_arrays(a: OpArrays, st_vec: jnp.ndarray, *, n_resources: int,
                    exact: bool = False,
                    f: FaultArrays | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (makespan, per-op completion times incl. lag). ``f=None``
    traces the exact pre-fault jaxpr (the healthy path never touches the
    fault arrays)."""
    fn = _sim_exact if exact else _sim_scan
    return fn(a, st_vec, n_resources, f)


def simulate(ops: MicroOps, st: ServiceTimes, *, exact: bool = False,
             timeline: bool = False) -> RunReport:
    """Drop-in equivalent of `ref_sim.simulate` running under XLA.

    ``timeline=True`` additionally attaches an `obs.timeline.Timeline`
    to the report: per-op start/end intervals recovered from the per-op
    completion times (start = end − lag − duration, both host-side
    recomputes of exactly what the device summed), in original op order.
    Its critical path explains the makespan — see the obs docs."""
    perm = None if exact else scan_order(ops, st)
    a = OpArrays.from_micro_ops(ops, perm=perm)
    fa = FaultArrays.from_micro_ops(ops, perm=perm) if faulted(ops) else None
    with enable_x64():
        makespan, end = simulate_arrays(a, jnp.asarray(st_to_vec(st)),
                                        n_resources=ops.n_resources, exact=exact,
                                        f=fa)
    end = np.asarray(end)
    if perm is not None:
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.shape[0], dtype=perm.dtype)
        end = end[inv]
    per_task = {tid: float(end[op]) for tid, op in ops.task_end_op.items()}
    per_stage: Dict[str, float] = {}
    for tid, t_end in per_task.items():
        s = ops.stage_of_task.get(tid, "")
        per_stage[s] = max(per_stage.get(s, 0.0), t_end)
    tl = None
    if timeline:
        from ..obs.timeline import Timeline
        from .ref_sim import durations as _ref_durations
        dur = _ref_durations(ops, st)        # fault-adjusted, host-side
        lag = ops.nlat * st.net_latency
        start = end - lag - dur
        tl = Timeline(start=start, dur=dur, lag=lag, end=end,
                      res=ops.res, cls=ops.cls, deps=ops.deps,
                      makespan=float(makespan),
                      n_resources=ops.n_resources)
    return RunReport(makespan=float(makespan), bytes_moved=ops.bytes_moved,
                     storage_used=ops.storage_used, per_task_end=per_task,
                     per_stage_end=per_stage, n_events=ops.n_ops,
                     timeline=tl)


# --- batched configuration sweeps (beyond-paper) -----------------------------------

@functools.partial(jax.jit, static_argnames=("n_resources", "exact"))
def _simulate_vmapped(batch: OpArrays, st_vecs: jnp.ndarray,
                      fbatch: FaultArrays | None = None, *, n_resources: int,
                      exact: bool = False) -> jnp.ndarray:
    def one(a, st, f=None):
        return simulate_arrays.__wrapped__(a, st, n_resources=n_resources,
                                           exact=exact, f=f)[0]
    if fbatch is None:
        return jax.vmap(one)(batch, st_vecs)
    return jax.vmap(one)(batch, st_vecs, fbatch)


def simulate_batch(ops_list: Sequence[MicroOps], st_list: Sequence[ServiceTimes],
                   *, exact: bool = False) -> np.ndarray:
    """Simulate C configurations in one vectorized XLA call.

    Pads every DAG to the batch max op count and resource count; padded
    ops are zero-duration no-ops on the dummy resource. This is the
    beyond-paper speedup: the paper runs one config per simulator run;
    here the sweep is a single `jit(vmap(...))`. A fault axis rides
    along: if any DAG carries a scenario, the batch gets stacked
    `FaultArrays` (neutral for healthy rows — exact multiply-by-one, so
    those rows stay element-wise identical to an all-healthy batch).
    """
    assert len(ops_list) == len(st_list)
    n_max = max(o.n_ops for o in ops_list)
    r_max = max(o.n_resources for o in ops_list)
    perms = [None if exact else scan_order(o, s)
             for o, s in zip(ops_list, st_list)]
    arrays = [OpArrays.from_micro_ops(o, pad_to=n_max, perm=p)
              for o, p in zip(ops_list, perms)]
    with enable_x64():
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)
        fbatch = None
        if any(faulted(o) for o in ops_list):
            farrs = [FaultArrays.from_micro_ops(o, n_resources=r_max,
                                                pad_to=n_max, perm=p)
                     for o, p in zip(ops_list, perms)]
            fbatch = jax.tree.map(lambda *xs: jnp.stack(xs), *farrs)
        st_vecs = jnp.asarray(np.stack([st_to_vec(s) for s in st_list]))
        return np.asarray(_simulate_vmapped(batch, st_vecs, fbatch,
                                            n_resources=r_max, exact=exact))


def sweep_service_times(ops: MicroOps, st_vecs: np.ndarray, *,
                        st_ref: ServiceTimes | None = None,
                        exact: bool = False) -> np.ndarray:
    """What-if hardware sweep (§2.1): one DAG, many ServiceTimes vectors."""
    perm = None
    if not exact:
        from .types import PAPER_RAMDISK
        perm = scan_order(ops, st_ref or PAPER_RAMDISK)
    a = OpArrays.from_micro_ops(ops, perm=perm)
    with enable_x64():
        def bcast(x):
            return jnp.broadcast_to(x, (st_vecs.shape[0],) + x.shape)
        batch = jax.tree.map(bcast, a)
        fbatch = None
        if faulted(ops):
            fbatch = jax.tree.map(
                bcast, FaultArrays.from_micro_ops(ops, perm=perm))
        return np.asarray(_simulate_vmapped(batch, jnp.asarray(st_vecs), fbatch,
                                            n_resources=ops.n_resources, exact=exact))
