"""Multi-process sweep dispatch tests (repro.core.sweep.multiproc).

The headline property is differential: for any sweep, at any worker
count — including class counts that straddle the worker-count boundary —
the multiproc path is **element-wise identical** to the in-process
engine, in both scan and exact mode. On top of that sit the warm-start
counters: a fleet reloading a pre-populated `CompileCache(path=...)`
performs zero `compile_workflow` executions (counter-asserted via each
worker's `compile_count()` delta), and a cold disk-backed fleet compiles
each structural class exactly once across all workers.

Worker pools are shared process-wide (spawn + jax import ~2s per
worker); tests that assert worker-side compile counters call
`shutdown_pools()` first to force memory-cold workers. Property tests
use hypothesis when installed and seeded deterministic draws otherwise.
"""
import os
import time
from concurrent.futures import Future
from concurrent.futures.process import BrokenProcessPool

import numpy as np
import pytest

from repro.core import (MB, PAPER_RAMDISK, CompileCache, Predictor,
                        SweepEngine, SysIdReport, explore, explore_many,
                        grid, successive_halving)
from repro.core.compile import compile_count, compile_workflow
from repro.core.sweep import multiproc
from repro.core.sweep.multiproc import (MultiprocSweep, SysIdServiceTimes,
                                        partition_weighted, shutdown_pools)
from repro.core import workloads as W

from test_core_sim import make_random_workflow

try:
    from hypothesis import given, settings, strategies as hst
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    HAVE_HYPOTHESIS = False

ST = PAPER_RAMDISK

# the CI multiproc leg sets this to run the differential suite at an
# operator-chosen fan-out (ci.yml: REPRO_SWEEP_WORKERS=2)
# `or "0"`: ci.yml defines the variable on every leg, as the empty
# string on the legs that don't opt in
ENV_WORKERS = int(os.environ.get("REPRO_SWEEP_WORKERS") or "0")


def blast_wf(c):
    return W.blast(c.n_app, n_queries=12, db_mb=32, per_query_s=1.0)


def small_grid():
    return grid(n_nodes=[7], chunk_sizes=[512 * 1024, 1 * MB])


def makespans(evals):
    return [e.makespan for e in evals]


# ---------------- partitioner ----------------------------------------------------

def check_partition(weights, n_items):
    runs = partition_weighted(weights, n_items)
    flat = [i for run in runs for i in run]
    assert flat == list(range(len(weights)))        # order-stable, complete
    assert all(run for run in runs)                 # non-empty items
    if weights:
        assert 1 <= len(runs) <= min(n_items, len(weights))
    assert runs == partition_weighted(weights, n_items)   # deterministic


def test_partition_weighted_straddles_worker_boundaries():
    # class counts that do not divide the item count, the empty sweep,
    # single-class sweeps, and heavily skewed weights
    for weights, n_items in [([1] * 5, 2), ([1] * 5, 3), ([1] * 7, 3),
                             ([1] * 2, 4), ([3], 2), ([], 2),
                             ([100, 1, 1, 1], 2), ([1, 1, 1, 100], 3)]:
        check_partition(weights, n_items)


if HAVE_HYPOTHESIS:
    @given(hst.lists(hst.integers(min_value=1, max_value=50), max_size=40),
           hst.integers(min_value=1, max_value=8))
    @settings(max_examples=100, deadline=None)
    def test_partition_weighted_property(weights, n_items):
        check_partition(weights, n_items)
else:
    def test_partition_weighted_property():
        rng = np.random.default_rng(7)
        for _ in range(100):
            n = int(rng.integers(0, 40))
            weights = [int(w) for w in rng.integers(1, 50, size=n)]
            check_partition(weights, int(rng.integers(1, 8)))


# ---------------- differential: multiproc == in-process ---------------------------

def test_explore_multiproc_bit_identical_two_workers():
    cands = small_grid()
    base = explore(blast_wf, cands, ST, verify_top_k=3,
                   engine=SweepEngine(), compile_cache=CompileCache())
    eng = SweepEngine()
    mp = explore(blast_wf, cands, ST, verify_top_k=3, engine=eng,
                 compile_cache=CompileCache(), workers=2)
    assert [e.candidate for e in base] == [e.candidate for e in mp]
    np.testing.assert_array_equal(makespans(base), makespans(mp))
    assert [e.verified for e in base] == [e.verified for e in mp]
    assert eng.stats.mp_items > 0


def test_explore_many_multiproc_three_workers_straddling():
    # 5 workflows x 2 candidates -> a class count that straddles the
    # 3-worker boundary; scan and the per-group exact shortlists both
    # run through the fleet
    wfs = [W.blast(2, n_queries=q, db_mb=16, per_query_s=1.0)
           for q in (4, 6, 8, 10, 12)]
    cands = grid(n_nodes=[7], chunk_sizes=[1 * MB], partitions=[(2, 4), (4, 2)])
    base = explore_many(wfs, cands, ST, verify_top_k=1,
                        engine=SweepEngine(), compile_cache=CompileCache())
    mp = explore_many(wfs, cands, ST, verify_top_k=1, engine=SweepEngine(),
                      compile_cache=CompileCache(), workers=3)
    for g_base, g_mp in zip(base, mp):
        assert [e.candidate for e in g_base] == [e.candidate for e in g_mp]
        np.testing.assert_array_equal(makespans(g_base), makespans(g_mp))
        assert [e.verified for e in g_base] == [e.verified for e in g_mp]


def test_successive_halving_multiproc_matches():
    cands = small_grid()
    base = successive_halving(blast_wf, cands, ST, engine=SweepEngine(),
                              compile_cache=CompileCache())
    mp = successive_halving(blast_wf, cands, ST, engine=SweepEngine(),
                            compile_cache=CompileCache(), workers=2)
    assert [e.candidate for e in base] == [e.candidate for e in mp]
    np.testing.assert_array_equal(makespans(base), makespans(mp))
    assert all(e.verified for e in mp)


def check_simulate_matches_engine(seeds, exact):
    """MultiprocSweep.simulate vs SweepEngine.simulate_batch on a batch
    of random workflows (batch sizes straddle the 2-worker boundary via
    the seed-list lengths)."""
    pairs = [make_random_workflow(np.random.default_rng(s)) for s in seeds]
    wfs = [w for w, _ in pairs]
    cfgs = [c for _, c in pairs]
    ops = [compile_workflow(w, c) for w, c in pairs]
    want = SweepEngine().simulate_batch(ops, [ST] * len(ops), exact=exact)
    mp = MultiprocSweep(wfs, cfgs, st=ST, workers=2, engine=SweepEngine(),
                        cache=CompileCache())
    got = mp.simulate(exact=exact)
    np.testing.assert_array_equal(want, got)


if HAVE_HYPOTHESIS:
    @given(hst.lists(hst.integers(min_value=0, max_value=2 ** 16),
                     min_size=1, max_size=5),
           hst.booleans())
    @settings(max_examples=8, deadline=None)
    def test_simulate_property_random_workflows(seeds, exact):
        check_simulate_matches_engine(seeds, exact)
else:
    def test_simulate_property_random_workflows():
        rng = np.random.default_rng(3)
        for n in (1, 2, 3, 5):
            seeds = [int(s) for s in rng.integers(0, 2 ** 16, size=n)]
            check_simulate_matches_engine(seeds, exact=bool(n % 2))


@pytest.mark.skipif(ENV_WORKERS < 2, reason="REPRO_SWEEP_WORKERS not set")
def test_explore_at_env_worker_count():
    """CI leg: the same differential property at the fan-out the matrix
    leg requests (--workers 2 in ci.yml)."""
    cands = small_grid()
    base = explore(blast_wf, cands, ST, verify_top_k=3,
                   engine=SweepEngine(), compile_cache=CompileCache())
    mp = explore(blast_wf, cands, ST, verify_top_k=3, engine=SweepEngine(),
                 compile_cache=CompileCache(), workers=ENV_WORKERS)
    np.testing.assert_array_equal(makespans(base), makespans(mp))


# ---------------- warm-start + compile counters -----------------------------------

def test_prepopulated_disk_cache_workers_compile_nothing(tmp_path):
    """The PR 4 fresh-process disk-cache property, fleet edition: workers
    reloading a pre-populated `CompileCache(path=...)` perform ZERO
    `compile_workflow` executions — counter-asserted via each worker's
    own `compile_count()` delta, rolled up into `worker_compiles`."""
    cands = small_grid()
    CompileCache(path=tmp_path).compile_grid(blast_wf, cands)   # pre-populate
    shutdown_pools()                                  # force memory-cold workers
    cache = CompileCache(path=tmp_path)
    eng = SweepEngine()
    n0 = compile_count()
    mp = explore(blast_wf, cands, ST, verify_top_k=3, engine=eng,
                 compile_cache=cache, workers=2)
    assert compile_count() == n0                      # parent compiled nothing
    assert sum(cache.stats.worker_compiles.values()) == 0   # ...nor any worker
    assert cache.stats.disk_hits >= 1                 # served from the shared dir
    assert eng.stats.mp_fallbacks == 0
    base = explore(blast_wf, cands, ST, verify_top_k=3,
                   engine=SweepEngine(), compile_cache=CompileCache())
    np.testing.assert_array_equal(makespans(base), makespans(mp))


def test_cold_fleet_compiles_each_class_exactly_once(tmp_path):
    """Cold disk-backed fleet: classes are partitioned whole, so the
    per-worker compile counts sum to the deduped structural-class count
    (the verify round disk-hits instead of recompiling)."""
    shutdown_pools()
    cache = CompileCache(path=tmp_path)
    groups = explore_many(
        [W.blast(2, n_queries=q, db_mb=16, per_query_s=1.0)
         for q in (4, 6, 8)],
        grid(n_nodes=[7], chunk_sizes=[512 * 1024, 1 * MB],
             partitions=[(2, 4)]),
        ST, verify_top_k=1, engine=SweepEngine(), compile_cache=cache,
        workers=2)
    assert all(any(e.verified for e in g) for g in groups)
    assert sum(cache.stats.worker_compiles.values()) == cache.stats.grid_classes
    assert len(cache.stats.worker_compiles) <= 2


def test_worker_rows_rollup():
    eng = SweepEngine()
    cache = CompileCache()
    explore(blast_wf, small_grid(), ST, verify_top_k=2, engine=eng,
            compile_cache=cache, workers=2)
    assert 1 <= len(eng.stats.worker_rows) <= 2
    # every padded row this engine accounts for was simulated by a worker
    assert sum(eng.stats.worker_rows.values()) == eng.stats.padded_rows
    assert eng.stats.sims == len(small_grid()) + 2  # scan + exact shortlist
    assert eng.stats.exact_sims == 2


def test_workers_one_degrades_to_in_process():
    eng = SweepEngine()
    explore(blast_wf, small_grid(), ST, verify_top_k=2, engine=eng,
            compile_cache=CompileCache(), workers=1)
    assert eng.stats.mp_items == 0
    assert not eng.stats.worker_rows
    assert eng.stats.batch_calls >= 1               # ran on this engine


def test_engine_workers_is_the_default_fanout():
    eng = SweepEngine(workers=2)
    mp = explore(blast_wf, small_grid(), ST, verify_top_k=2, engine=eng,
                 compile_cache=CompileCache())       # no workers= kwarg
    assert eng.stats.mp_items > 0
    base = explore(blast_wf, small_grid(), ST, verify_top_k=2,
                   engine=SweepEngine(), compile_cache=CompileCache())
    np.testing.assert_array_equal(makespans(base), makespans(mp))


def test_predictor_workers_matches_in_process():
    cands = small_grid()
    wfs = [blast_wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]
    base = Predictor(ST, compile_cache=CompileCache()).predict_batch(wfs, cfgs)
    got = Predictor(ST, compile_cache=CompileCache(),
                    workers=2).predict_batch(wfs, cfgs)
    np.testing.assert_array_equal(base, got)


# ---------------- sysid warm-start ------------------------------------------------

def test_sysid_report_reference_resolves_in_workers(tmp_path):
    """Workers warm-start service times from the persisted SysIdReport
    cache (one load per worker) instead of unpickling them; the parent's
    in-process path resolves the same reference."""
    path = tmp_path / "sysid.json"
    SysIdReport(service_times=ST, n_measurements=1, details={}).save(path)
    ref = SysIdServiceTimes(str(path))
    cands = small_grid()
    base = explore(blast_wf, cands, ST, verify_top_k=2,
                   engine=SweepEngine(), compile_cache=CompileCache())
    via_ref_mp = explore(blast_wf, cands, ref, verify_top_k=2,
                         engine=SweepEngine(), compile_cache=CompileCache(),
                         workers=2)
    via_ref_local = explore(blast_wf, cands, ref, verify_top_k=2,
                            engine=SweepEngine(), compile_cache=CompileCache())
    np.testing.assert_array_equal(makespans(base), makespans(via_ref_mp))
    np.testing.assert_array_equal(makespans(base), makespans(via_ref_local))


# ---------------- degraded fleet --------------------------------------------------

def test_item_timeout_falls_back_in_process():
    """An expired item deadline degrades that item to the parent engine
    (values unchanged) without tearing down the healthy pool."""
    cands = small_grid()
    wfs = [blast_wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]
    eng = SweepEngine()
    mp = MultiprocSweep(wfs, cfgs, st=ST, workers=2, engine=eng,
                        cache=CompileCache(), item_timeout_s=1e-9)
    got = mp.simulate()
    assert eng.stats.mp_fallbacks > 0
    ops = [compile_workflow(w, c) for w, c in zip(wfs, cfgs)]
    want = SweepEngine().simulate_batch(ops, [ST] * len(ops))
    np.testing.assert_array_equal(want, got)
    assert multiproc._POOLS                         # pool survived


def test_broken_pool_falls_back_in_process(monkeypatch):
    """A dead pool must degrade the sweep, not fail it: every item runs
    in-process through the parent engine, results unchanged."""
    class BrokenPool:
        def submit(self, *a, **kw):
            raise RuntimeError("cannot schedule new futures after shutdown")

    monkeypatch.setattr(multiproc, "_get_pool", lambda workers: BrokenPool())
    cands = small_grid()
    eng = SweepEngine()
    mp = explore(blast_wf, cands, ST, verify_top_k=2, engine=eng,
                 compile_cache=CompileCache(), workers=2)
    assert eng.stats.mp_fallbacks > 0
    assert not eng.stats.worker_rows                # nothing ran remotely
    base = explore(blast_wf, cands, ST, verify_top_k=2,
                   engine=SweepEngine(), compile_cache=CompileCache())
    np.testing.assert_array_equal(makespans(base), makespans(mp))


# ---------------- slow/hung-worker regression tier --------------------------------
#
# Fake pools, no real processes: each future's state is scripted, so the
# merge loop's deadline arithmetic, respawn accounting, and late-drop
# counting are exercised deterministically (and without waiting on spawn
# + jax import). The fallback path is the real one — parent cache,
# parent engine — so the values asserts are real too.

class FakePool:
    def __init__(self, make_future):
        self._make = make_future

    def submit(self, fn, *a, **kw):
        return self._make()


class FakeHandle:
    """Quacks like `PoolHandle` (``executor()``/``respawn()``) but vends
    scripted futures and counts respawns."""

    def __init__(self, make_future):
        self._pool = FakePool(make_future)
        self.respawns = 0

    def executor(self):
        return self._pool

    def respawn(self):
        self.respawns += 1


def degraded_mp(eng, cache, make_future, **kw):
    """A MultiprocSweep over `small_grid` whose pool vends scripted
    futures, plus the in-process reference answer."""
    cands = small_grid()
    wfs = [blast_wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]
    handle = FakeHandle(make_future)
    mp = MultiprocSweep(wfs, cfgs, st=ST, workers=2, engine=eng,
                        cache=cache, pool=handle, **kw)
    ops = [compile_workflow(w, c) for w, c in zip(wfs, cfgs)]
    want = SweepEngine().simulate_batch(ops, [ST] * len(ops))
    return mp, handle, want


def test_hung_worker_merge_completes_in_o_timeout():
    """THE deadline regression: with ``item_timeout_s`` set, a merge
    over N items of hung workers completes in O(timeout), not
    O(N x timeout) — every item's deadline clock starts at submit, so
    the expirations overlap instead of serializing through the merge
    loop (pre-fix, the verbatim ``fut.result(timeout=item_timeout_s)``
    restarted each item's clock when the loop reached it)."""
    eng, cache = SweepEngine(), CompileCache()
    # warm pass: same item shapes, ~zero budget — pays the DAG compiles
    # and bucket executables so the timed pass measures only deadlines
    mp0, _, want = degraded_mp(eng, cache, Future, item_timeout_s=1e-9)
    np.testing.assert_array_equal(want, mp0.simulate())
    timeout = 1.0
    mp, handle, want = degraded_mp(eng, cache, Future,
                                   item_timeout_s=timeout)
    before = eng.stats.mp_items
    t0 = time.perf_counter()
    got = mp.simulate()
    dt = time.perf_counter() - t0
    n_items = eng.stats.mp_items - before
    assert n_items >= 3                    # O(timeout) vs O(N x timeout)
    np.testing.assert_array_equal(want, got)
    assert dt < 2.5 * timeout              # pre-fix: >= n_items * timeout
    assert handle.respawns == 0            # timeouts never churn the pool
    assert eng.stats.mp_late_drops == 0    # pending futures cancel cleanly


def test_broken_generation_respawns_pool_exactly_once():
    """Every item of a broken dispatch generation raises BrokenExecutor
    at harvest; the pool is respawned ONCE — not once per item — and the
    whole sweep completes in-process with identical values."""
    def broken_future():
        f = Future()
        f.set_exception(BrokenProcessPool("worker died"))
        return f

    eng, cache = SweepEngine(), CompileCache()
    mp, handle, want = degraded_mp(eng, cache, broken_future)
    got = mp.simulate()
    np.testing.assert_array_equal(want, got)
    assert handle.respawns == 1
    assert eng.stats.mp_fallbacks == eng.stats.mp_items >= 2
    assert eng.stats.mp_late_drops == 0


def test_late_result_after_failed_cancel_is_counted():
    """A timed-out item whose worker already started (``cancel()``
    fails) re-runs in-process; the worker's eventual result — values and
    counter rollup — is dropped, and the drop is counted so worker
    counter asserts know to stand down."""
    def running_future():
        f = Future()
        assert f.set_running_or_notify_cancel()   # cancel() now fails
        return f

    eng, cache = SweepEngine(), CompileCache()
    mp, handle, want = degraded_mp(eng, cache, running_future,
                                   item_timeout_s=1e-3)
    got = mp.simulate()
    np.testing.assert_array_equal(want, got)
    assert eng.stats.mp_late_drops == eng.stats.mp_items > 0
    assert eng.stats.mp_fallbacks == eng.stats.mp_items
    assert handle.respawns == 0


def test_spawned_worker_is_pinned_to_cpu(monkeypatch):
    """Workers are host workers: on an accelerator host the parent holds
    the chip, so a worker must never reach for it. The spawned child
    inherits a platform it cannot use; `_worker_init` must pin the CPU
    before the worker's first computation."""
    import jax
    monkeypatch.setenv("JAX_PLATFORMS", "no-such-platform")
    handle = multiproc.PoolHandle(1)
    try:
        platform = handle.executor().submit(jax.default_backend) \
            .result(timeout=120)
    finally:
        handle.close()
    assert platform == "cpu"
