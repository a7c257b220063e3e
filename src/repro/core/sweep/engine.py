"""Compile-cached batched simulation engine — the sweep stack's
*executor*.

The sweep hot path is `jit(vmap(simulate))` over a batch of padded DAGs.
This engine owns the executables: one per ``(n_ops_bucket,
n_resources_bucket, batch_bucket, exact, n_shards, faulted)`` key, held
in a small LRU. Because the bucket fully determines every array shape
entering the executable, a cache hit is guaranteed to be an XLA-cache
hit too — a second sweep over a same-bucket grid performs zero new
compiles (the acceptance property `tests/test_sweep.py` asserts via the
hit/miss counters).

The engine executes; it does not own policy or lifecycle. *What* runs
where is decided one layer up by an `ExecutionBackend`
(`sweep.backends`: inline / device-sharded / multi-process), and *state*
— which engine, which compile cache, which mesh, which worker pools —
is owned by a `SweepSession` (`sweep.session`). ``set_mesh`` points the
engine at an already-resolved device mesh (the `ShardedBackend` resolves
it); bucket batches are then partitioned over the mesh via
`shard.sharded_executable`, so grid throughput scales with device count
instead of being bound by one device (docs/sweep.md, "Sharded
execution"). Placement is adaptive: a bucket is sharded only when it
carries at least ``min_shard_oprows`` real op-rows (candidates x padded
op count), because tiny buckets are dispatch-bound and run *slower*
split eight ways. Batches that don't divide the device count are padded
into the existing power-of-two buckets (``shard.shard_pad``), never
recompiled.

Below the executables sit three host-side caches that keep warm sweeps
device-bound (the Python prep — `scan_order` + padding + host->device
transfer — otherwise dwarfs the simulation itself):

* a **level cache** of each DAG's topological level partition
  (`jax_sim.dag_levels`), keyed by DAG identity — a what-if loop, whose
  new service times miss every row, reorders a known DAG with the
  vectorized level pass alone;
* a **row cache** of prepped `OpArrays`, keyed by (DAG identity, service
  times, ops bucket, exact) — subset re-sweeps (halving rounds, what-if
  loops) skip `scan_order` and padding for every row seen before;
* a **batch cache** of stacked bucket batches, keyed by the row keys —
  an identical re-sweep skips stacking and host->device transfer
  entirely.

Counters track exact-mode usage (the search layer proves it verifies
shortlists with one batched call per round), level/row/batch cache
traffic, the rows `scan_order` ordered by each path,
and per-device placement (``device_rows``) so sharded runs can show
where rows actually ran.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.sweep_scan import ops as sweep_scan_ops
from ...obs.trace import NULL_TRACER
from ..compile import MicroOps
from ..types import ServiceTimes
from ..x64 import enable_x64
from .. import jax_sim
from .buckets import group_by_bucket
from . import shard as _shard

# key: (n_ops_bucket, n_resources_bucket, batch_bucket, exact, n_shards,
#       faulted, kernel) — faulted buckets trace a third FaultArrays
# argument, so they are a distinct structural class from healthy ones;
# kernel marks scan executables built on the fused Pallas sweep_scan
# kernel rather than the XLA lax.scan body (`set_mesh` filters on
# k[4] == 1 shards unchanged, benchmarks count faulted buckets via k[5])
CacheKey = Tuple[int, int, int, bool, int, bool, bool]

# the engine's ``sim_engine`` knob: what the scan-mode executable body is
# built on. "auto" takes the Pallas kernel where the platform runs it
# (CPU, interpret mode) and the XLA lax.scan body elsewhere — on TPU by
# design, since Mosaic refuses the kernel (`sweep_scan.ops.TPU_REFUSAL`);
# "pallas" insists (raising where the kernel cannot run); "xla" keeps the
# plain lax.scan body. Exact mode always runs the XLA while_loop — the
# kernel is scan-only.
SIM_ENGINES = ("auto", "pallas", "xla")

# DAGs whose level partition (`jax_sim.dag_levels`) the engine keeps: the
# DAG cache's default capacity, so every DAG a session's sweeps reuse
# keeps its levels
MAX_LEVEL_ENTRIES = 256

# a sharded bucket must carry at least this many real op-rows
# (candidates x padded op count); below it the per-device dispatch
# overhead exceeds the parallelism win (measured on 8 forced host
# devices: small buckets run 4-15x SLOWER sharded, large ones 2-5x
# faster — the boundary sits around 2^15 op-rows)
MIN_SHARD_OPROWS = 32768


@dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    batch_calls: int = 0          # simulate_batch invocations
    exact_batch_calls: int = 0    # ... with exact=True
    sims: int = 0                 # candidate-simulations served (REQUESTED
                                  # candidates — never the padded row count)
    exact_sims: int = 0
    padded_rows: int = 0          # rows actually simulated incl. padding
    row_hits: int = 0             # prepped-OpArrays cache traffic
    row_misses: int = 0
    level_hits: int = 0           # per-DAG level-partition cache traffic
    level_misses: int = 0         # (`jax_sim.dag_levels`, for scan_order)
    level_rows: int = 0           # rows scan_order ordered level by level
    loop_rows: int = 0            # ... op by op (small, deep or
                                  # non-topological DAGs)
    stack_hits: int = 0           # stacked-bucket-batch cache traffic
    stack_misses: int = 0
    sharded_batch_calls: int = 0  # simulate_batch calls that sharded >= 1 bucket
    device_rows: Dict[str, int] = field(default_factory=dict)
                                  # rows placed per device (padded), sharded only
    mp_items: int = 0             # work items dispatched to worker processes
    mp_fallbacks: int = 0         # items a dead worker pushed back in-process
    mp_late_drops: int = 0        # timed-out items whose worker was already
                                  # running (cancel failed): the late result —
                                  # values AND counter rollup — was discarded
                                  # while the item re-ran in-process, so
                                  # worker-counter asserts must not be hard
                                  # while this is nonzero (the late worker may
                                  # also still be writing the shared disk cache)
    kernel_buckets: int = 0       # executables built on the Pallas sweep_scan
                                  # kernel (scan mode, sim_engine auto/pallas)
    kernel_fallbacks: int = 0     # scan batches that wanted the kernel
                                  # (sim_engine="auto") but fell back to XLA
                                  # because it cannot run here; the TPU's XLA
                                  # body is its platform's path, not counted
    worker_rows: Dict[str, int] = field(default_factory=dict)
                                  # rows simulated per worker process (padded) —
                                  # the multiproc sibling of device_rows

    def reset(self) -> None:
        # derived from the dataclass fields, never a hand-maintained
        # tuple: a counter added tomorrow resets (and flows into
        # `obs.export.stats_snapshot`) without anyone remembering to
        # list it here (regression-tested in tests/test_obs.py)
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, dict):
                v.clear()
            else:
                setattr(self, f.name, 0)


def _make_executable(n_resources: int, exact: bool, mesh=None,
                     faulted: bool = False, kernel: bool = False):
    if kernel and not exact:
        # fused scan path: durations stay a cheap vmapped elementwise
        # prologue in XLA; the sequential FIFO recurrence runs as ONE
        # Pallas kernel over the whole candidate batch (grid = batch x
        # op-row blocks) instead of a vmap of lax.scan — element-wise
        # identical by construction (kernels/sweep_scan shares its
        # serving recurrence with jax_sim._scan_once)
        def scan_batch(batch: jax_sim.OpArrays, st_vecs: jnp.ndarray,
                       fbatch: "jax_sim.FaultArrays | None" = None):
            if fbatch is None:
                dur, lag = jax.vmap(
                    lambda a, st: jax_sim._durations(a, st))(batch, st_vecs)
            else:
                dur, lag = jax.vmap(jax_sim._durations)(batch, st_vecs,
                                                        fbatch)
            return sweep_scan_ops.sweep_scan(
                batch.res, dur, lag, batch.deps,
                n_resources=n_resources, use_kernel=True)[0]

        fn = scan_batch
    else:
        body = jax_sim._sim_exact if exact else jax_sim._sim_scan

        if faulted:
            def sim(a: jax_sim.OpArrays, st_vec: jnp.ndarray,
                    f: jax_sim.FaultArrays) -> jnp.ndarray:
                return body(a, st_vec, n_resources, f)[0]
        else:
            def sim(a: jax_sim.OpArrays, st_vec: jnp.ndarray) -> jnp.ndarray:
                return body(a, st_vec, n_resources)[0]

        # the executable's stable name in a device trace: jit_sim_scan
        # or jit_sim_exact, whatever the bucket or fault state
        sim.__name__ = sim.__qualname__ = \
            "sim_exact" if exact else "sim_scan"
        fn = jax.vmap(sim)
    if mesh is not None:
        return _shard.sharded_executable(fn, mesh,
                                         n_args=3 if faulted else 2)
    return jax.jit(fn)


class SweepEngine:
    """Bucketed-padding batch simulator with an LRU of compiled sweeps.

    ``simulate_batch`` is a drop-in for `jax_sim.simulate_batch` (same
    signature and results) that routes each candidate through its shape
    bucket's cached executable rather than compiling for the batch max.

    ``devices`` selects sharded execution (`shard.resolve_mesh`
    semantics: None = single device, 0 = all visible, n = first n, or an
    explicit device list / 1-D mesh). Sharded and unsharded results are
    element-wise identical (tests/test_shard.py). ``min_shard_oprows``
    tunes the adaptive placement threshold (0 = always shard).

    ``sim_engine`` picks the scan-mode executable body (`SIM_ENGINES`):
    "auto" builds on the fused Pallas `kernels.sweep_scan` kernel where
    the platform runs it (CPU, interpret mode) and on the XLA lax.scan
    body elsewhere — TPU included, whose compiler refuses the kernel;
    "pallas" insists, and raises with the compiler's reason on TPU; "xla"
    opts out. The two bodies are element-wise identical
    (tests/test_sweep_kernel.py) — exact mode always runs the XLA
    while_loop.

    ``workers`` is the engine's default host-process fan-out: the search
    layer (`explore`/`explore_many`/`successive_halving`) and
    `Predictor.predict_batch` dispatch sweeps through
    `multiproc.MultiprocSweep` when it is > 1 and no per-call ``workers=``
    overrides it. The engine's own ``simulate_batch`` always runs
    in-process (it receives already-compiled DAGs; the multiproc layer
    dispatches (workflow, config) specs so workers can warm-start from
    the shared disk compile cache) — worker counters roll up into this
    engine's ``stats`` (``worker_rows``, ``mp_items``).
    """

    def __init__(self, max_entries: int = 32, *,
                 devices: _shard.DevicesLike = None,
                 min_shard_oprows: int = MIN_SHARD_OPROWS,
                 max_row_entries: int = 4096,
                 max_stack_entries: int = 32,
                 workers: int = 1,
                 sim_engine: str = "auto",
                 tracer=None):
        if sim_engine not in SIM_ENGINES:
            raise ValueError(f"sim_engine must be one of {SIM_ENGINES}, "
                             f"got {sim_engine!r}")
        self.max_entries = max_entries
        self.workers = max(int(workers), 1)
        self.sim_engine = sim_engine
        # wall-clock span recorder (obs.trace) — the no-op NULL_TRACER
        # unless a SweepSession(tracer=...) points it at a live one; the
        # instrumented path is identical either way (tests/test_obs.py
        # counter-asserts zero extra compiles / batch calls)
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.min_shard_oprows = min_shard_oprows
        self.max_row_entries = max_row_entries
        self.max_stack_entries = max_stack_entries
        self._fns: "OrderedDict[CacheKey, object]" = OrderedDict()
        # row key -> (ops ref, prepped OpArrays); holding the MicroOps
        # reference pins its id(), keeping the identity-based key sound
        self._rows: "OrderedDict[tuple, tuple]" = OrderedDict()
        # tuple of row keys (+ batch shape) -> stacked device batch
        self._stacks: "OrderedDict[tuple, object]" = OrderedDict()
        # id(ops) -> (ops ref, jax_sim.DagLevels): scan_order's structure,
        # which no service time changes; the reference pins the id()
        self._levels: "OrderedDict[int, tuple]" = OrderedDict()
        self._mesh = _shard.resolve_mesh(devices)
        self.stats = CacheStats()

    # -- device placement -----------------------------------------------------
    @property
    def mesh(self):
        return self._mesh

    @property
    def n_shards(self) -> int:
        return _shard.shard_count(self._mesh)

    def set_mesh(self, mesh) -> "SweepEngine":
        """Point the engine at an already-resolved 1-D mesh (or None for
        single-device). Sharded executables close over their mesh, so
        changing it drops them; plain (shards=1) entries survive. Mesh
        *resolution* (device counts, lists, pow2 prefixes) lives in the
        backend/session layer — see `shard.resolve_mesh`."""
        if _shard.mesh_identity(mesh) != _shard.mesh_identity(self._mesh):
            self._fns = OrderedDict(
                (k, fn) for k, fn in self._fns.items() if k[4] == 1)
            self._mesh = mesh
        return self

    def use_devices(self, devices: _shard.DevicesLike) -> "SweepEngine":
        """Legacy shim: resolve ``devices`` and `set_mesh` the result."""
        return self.set_mesh(_shard.resolve_mesh(devices))

    def bucket_shards(self, n_rows: int, n_ops_bucket: int) -> int:
        """Adaptive placement: shards for a bucket of ``n_rows`` real
        candidates whose DAGs pad to ``n_ops_bucket`` ops. 1 = keep the
        bucket on a single device (too little work to split)."""
        if self._mesh is None:
            return 1
        if n_rows * n_ops_bucket < self.min_shard_oprows:
            return 1
        return self.n_shards

    def _use_kernel(self, exact: bool) -> bool:
        """Resolve the ``sim_engine`` knob for one scan batch — at
        trace time, before the executable is built, so an unsupported
        backend never traces a Pallas call it cannot run. The refusal
        says whether its XLA body counts as a fallback."""
        if exact or self.sim_engine == "xla":
            return False
        refusal = sweep_scan_ops.kernel_refusal()
        if refusal is None:
            return True
        if self.sim_engine == "pallas":
            raise RuntimeError(
                "sim_engine='pallas' but the Pallas kernel cannot run on "
                f"backend {jax.default_backend()!r}: {refusal.reason}")
        if refusal.fallback:
            self.stats.kernel_fallbacks += 1
        return False

    # -- executable cache ------------------------------------------------------
    def _executable(self, key: CacheKey):
        fn = self._fns.get(key)
        if fn is not None:
            self.stats.hits += 1
            self._fns.move_to_end(key)
            return fn
        self.stats.misses += 1
        fn = _make_executable(n_resources=key[1], exact=key[3],
                              mesh=self._mesh if key[4] > 1 else None,
                              faulted=key[5], kernel=key[6])
        if key[6]:
            self.stats.kernel_buckets += 1
        self._fns[key] = fn
        if len(self._fns) > self.max_entries:
            self._fns.popitem(last=False)
            self.stats.evictions += 1
        return fn

    def cache_keys(self) -> List[CacheKey]:
        return list(self._fns)

    def release(self) -> None:
        """Drop every cached executable and host-prep entry, releasing
        the device buffers they pin. The engine stays usable — the next
        sweep simply recompiles. `SweepSession.close()` calls this."""
        self._fns.clear()
        self._rows.clear()
        self._stacks.clear()
        self._levels.clear()

    # -- host-prep caches ------------------------------------------------------
    def _dag_levels(self, ops: MicroOps) -> jax_sim.DagLevels:
        """The DAG's level partition, built on its first scan-mode row
        and reused for every service-time vector after it."""
        hit = self._levels.get(id(ops))
        if hit is not None:
            self.stats.level_hits += 1
            self._levels.move_to_end(id(ops))
            return hit[1]
        self.stats.level_misses += 1
        levels = jax_sim.dag_levels(ops)
        self._levels[id(ops)] = (ops, levels)
        if len(self._levels) > MAX_LEVEL_ENTRIES:
            self._levels.popitem(last=False)
        return levels

    def _prepped_row(self, ops: MicroOps, st: ServiceTimes, n_pad: int,
                     r_pad: int, exact: bool
                     ) -> Tuple[tuple, jax_sim.OpArrays,
                                Optional[jax_sim.FaultArrays]]:
        """Padded (and, in scan mode, permuted) device-side arrays for
        one DAG — the per-row Python cost a warm sweep must not repay.
        Exact mode never permutes, so its key is service-time free.
        Faulted DAGs also carry their `FaultArrays` (padded to the same
        bucket; ``r_pad`` sizes the multiplier vector, hence its place in
        the key); healthy DAGs carry None."""
        key = (id(ops), n_pad, r_pad, True) if exact else \
            (id(ops), n_pad, r_pad, False, jax_sim.st_to_vec(st).tobytes())
        hit = self._rows.get(key)
        if hit is not None:
            self.stats.row_hits += 1
            self._rows.move_to_end(key)
            return key, hit[1], hit[2]
        self.stats.row_misses += 1
        perm = None
        if not exact:
            with self.tracer.span("order", phase="host-order",
                                  ops=ops.n_ops):
                levels = self._dag_levels(ops)
                perm = jax_sim.scan_order(ops, st, levels)
            if levels.vectorized:
                self.stats.level_rows += 1
            else:
                self.stats.loop_rows += 1
        # padding, permuting, and each row's copy to the device
        with self.tracer.span("pack", phase="host-pack", ops=ops.n_ops):
            arr = jax_sim.OpArrays.from_micro_ops(ops, pad_to=n_pad,
                                                  perm=perm)
            farr = (jax_sim.FaultArrays.from_micro_ops(
                        ops, n_resources=r_pad, pad_to=n_pad, perm=perm)
                    if jax_sim.faulted(ops) else None)
        self._rows[key] = (ops, arr, farr)
        if len(self._rows) > self.max_row_entries:
            self._rows.popitem(last=False)
        return key, arr, farr

    def _stacked(self, row_keys: Tuple[tuple, ...], ops: List[MicroOps],
                 arrays: List[jax_sim.OpArrays],
                 farrs: Optional[List[Optional[jax_sim.FaultArrays]]],
                 vecs: np.ndarray, n_pad: int, r_pad: int):
        """Stacked bucket batch and the batch's service-time vectors
        ``vecs`` on the device; an identical re-sweep skips the
        stack + host->device transfer entirely. The entry pins the
        MicroOps references itself: row keys are id()-based, and a row
        entry may be evicted (releasing its pin) while the stack entry
        survives — a recycled id() must not serve a stale batch.

        ``farrs`` is None for all-healthy buckets; in a faulted bucket,
        healthy rows get a shared *neutral* `FaultArrays` (x1.0 / +0.0 —
        exact in f64, so those rows match the healthy path element-wise).
        The key needs no fault flag: row keys pin DAG identity, and a
        DAG's fault state is part of the DAG."""
        hit = self._stacks.get(row_keys)
        if hit is not None:
            self.stats.stack_hits += 1
            self._stacks.move_to_end(row_keys)
            return hit[1], hit[2], jnp.asarray(vecs)
        self.stats.stack_misses += 1
        # the host's part only: the stacks and the copy are dispatched
        # to the device asynchronously, and may finish after the span
        with self.tracer.span("stack", phase="host-stack",
                              rows=len(arrays)):
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *arrays)
            fbatch = None
            if farrs is not None:
                neutral = jax_sim.FaultArrays.neutral(n_pad, r_pad)
                fbatch = jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[f if f is not None else neutral for f in farrs])
            st_vecs = jnp.asarray(vecs)
        self._stacks[row_keys] = (tuple(ops), batch, fbatch)
        if len(self._stacks) > self.max_stack_entries:
            self._stacks.popitem(last=False)
        return batch, fbatch, st_vecs

    # -- simulation -----------------------------------------------------------
    def simulate_batch(self, ops_list: Sequence[MicroOps],
                       st_list: Sequence[ServiceTimes], *,
                       exact: bool = False) -> np.ndarray:
        """Makespans for C (DAG, ServiceTimes) pairs, bucketed + cached."""
        assert len(ops_list) == len(st_list)
        self.stats.batch_calls += 1
        # count REQUESTED candidates; padding is tracked in padded_rows
        self.stats.sims += len(ops_list)
        if exact:
            self.stats.exact_batch_calls += 1
            self.stats.exact_sims += len(ops_list)
        out = np.zeros(len(ops_list))
        if not ops_list:
            return out
        sharded_any = False
        use_kernel = self._use_kernel(exact)
        sim_phase = "exact-verify" if exact else "device-sim"
        with self.tracer.span("simulate_batch", phase=sim_phase,
                              candidates=len(ops_list), exact=exact), \
                enable_x64():
            for (n_pad, r_pad), idxs in group_by_bucket(ops_list).items():
                shards = self.bucket_shards(len(idxs), n_pad)
                sharded_any |= shards > 1
                # remainder handling: the batch bucket is a power of two
                # >= the shard count, so it always divides the mesh —
                # odd batch sizes reuse existing buckets, never recompile
                c_pad = _shard.shard_pad(len(idxs), shards)
                with self.tracer.span(f"prep[{n_pad}x{r_pad}]",
                                      phase="host-prep", rows=len(idxs)):
                    keyed = [self._prepped_row(ops_list[i], st_list[i],
                                               n_pad, r_pad, exact)
                             for i in idxs]
                    vecs = [jax_sim.st_to_vec(st_list[i]) for i in idxs]
                    # one faulted row makes the whole bucket faulted:
                    # healthy companions ride along on neutral arrays
                    # (exact) rather than splitting the bucket into two
                    # executables
                    faulted_b = any(f is not None for _, _, f in keyed)
                    # pad the batch axis by replicating the first row;
                    # the duplicates are sliced off below
                    keyed += [keyed[0]] * (c_pad - len(idxs))
                    vecs += [vecs[0]] * (c_pad - len(idxs))
                    batch, fbatch, st_vecs = self._stacked(
                        tuple(k for k, _, _ in keyed),
                        [ops_list[i] for i in idxs],
                        [a for _, a, _ in keyed],
                        [f for _, _, f in keyed] if faulted_b else None,
                        np.stack(vecs), n_pad, r_pad)
                with self.tracer.span(f"sim[{n_pad}x{r_pad}x{c_pad}]",
                                      phase=sim_phase, rows=len(idxs),
                                      shards=shards, faulted=faulted_b):
                    fn = self._executable((n_pad, r_pad, c_pad, exact,
                                           shards, faulted_b, use_kernel))
                    res = fn(batch, st_vecs, fbatch) if faulted_b \
                        else fn(batch, st_vecs)
                    # np.asarray blocks on the device result, so the span
                    # covers real execution, not async dispatch
                    out[idxs] = np.asarray(res)[:len(idxs)]
                self.stats.padded_rows += c_pad
                if shards > 1:
                    rows_per_dev = c_pad // shards
                    for d in np.ravel(self._mesh.devices):
                        key = str(d)
                        self.stats.device_rows[key] = \
                            self.stats.device_rows.get(key, 0) + rows_per_dev
        if sharded_any:
            self.stats.sharded_batch_calls += 1
        return out
