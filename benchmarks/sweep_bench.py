"""Sweep-engine benchmarks: the two-level cache payoff and the new
scenario-diversity workloads.

`sweepcache` times the same Scenario-I grid twice through one
`SweepSession` — the first sweep pays the XLA compiles for every shape
bucket it touches, the second hits the executable cache for all of them
— and reports the warm/cold speedup plus the counter evidence.
`sweepcompile` measures the DAG-level cache above it: a full cold
`explore` (Python `compile_workflow` per structural class + XLA
compiles) against a warm repeat of the same grid, counter-asserting
that the warm sweep executes `compile_workflow` exactly zero times.
`sweepscenarios` sweeps the scatter_gather and map_reduce_shuffle
workloads and cross-checks the verified winner against `ref_sim`.
`sweepshard` measures device-sharded execution: the same ≥256-candidate
grid through an inline session and a `ShardedBackend` one (sharing one
DAG cache), reporting per-session throughput and the scaling factor (run
it under XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU-only
hosts).
`sweeptrace` exercises the trace front-end: shipped fixture ingestion
(scan-vs-exact agreement) plus a ≥16-member generated-family sweep
through `explore_many`, counter-asserting that structural dedup compiles
strictly fewer DAGs than family-size x grid-size.
`sweepfaults` sweeps the Montage fixture crossed with degraded-disk and
node-kill scenarios (docs/faults.md), hard-asserting the fault axis's
acceptance property — replication=2 wins under the faults it exists
for, replication=1 wins the healthy subset of the same run — plus
faulted-bucket compile counters and a zero-compile bit-identical warm
repeat.
`sweepmp` measures the multi-process host fan-out: the same trace-family
sweep through a `MultiprocBackend` session owning a 2-worker spawn fleet
vs one process, hard-asserting bit-identical output, per-worker compile
counts summing to the deduped structural-class count, and a zero-compile
warm fleet repeat.
`sweepcompile`, `sweeptrace` and `sweepscenarios` deliberately stay on
the legacy ``engine=``/``compile_cache=``/``workers=`` kwargs — they are
the shim-coverage half of the benchmark suite.
"""
from __future__ import annotations

import os
import tempfile
import time
from pathlib import Path
from typing import List

import numpy as np

from repro.core import (MB, PAPER_HDD, PAPER_RAMDISK, CompileCache,
                        DiskDegradation, FaultScenario, MultiprocBackend,
                        NodeFailure, Predictor, ShardedBackend, SweepEngine,
                        SweepSession, explore, explore_many, grid, ref_sim,
                        with_faults)
from repro.core.compile import compile_count, compile_workflow
from repro.core.sweep import resolve_mesh, shard_count
from repro.core.trace import GenSpec, generate_family, load_trace, to_workflow
from repro.core import workloads as W

from .common import Row

TRACES_DIR = Path(__file__).resolve().parents[1] / "examples" / "traces"


def sweep_cache() -> List[Row]:
    st = PAPER_RAMDISK
    cands = grid(n_nodes=[12, 16], chunk_sizes=[256 * 1024, 1 * MB])
    wf = lambda c: W.blast(c.n_app, n_queries=24, db_mb=64, per_query_s=2.0)
    wfs = [wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]

    with SweepSession() as sess:
        # pre-warm the DAG cache so the cold timing isolates the XLA
        # compiles the executable cache then removes
        sess.compile_cache.compile_grid(wf, cands)
        run = sess.prepare(wfs, cfgs, st=st)

        t0 = time.monotonic()
        run.simulate()
        cold = time.monotonic() - t0
        misses = sess.stats.misses

        t0 = time.monotonic()
        run.simulate()
        warm = time.monotonic() - t0
        new_misses = sess.stats.misses - misses

        return [
            Row("sweepcache/cold_s", cold,
                f"{len(cands)} configs, {misses} bucket compiles"),
            Row("sweepcache/warm_s", warm,
                f"hits={sess.stats.hits} new_compiles={new_misses}"),
            Row("sweepcache/speedup_x", cold / max(warm, 1e-9),
                f"zero_new_compiles={new_misses == 0}"),
        ]


def sweep_compile() -> List[Row]:
    """Cold-vs-warm full `explore` with the structure-keyed DAG cache.

    The warm sweep must perform ZERO `compile_workflow` executions (the
    process-wide `compile_count` counter is the ground truth, asserted
    here) and must return bit-identical evaluations.
    """
    st = PAPER_RAMDISK
    eng = SweepEngine()
    cache = CompileCache()
    cands = grid(n_nodes=[12, 16], chunk_sizes=[256 * 1024, 1 * MB],
                 stripe_widths=[0, 4])
    wf = lambda c: W.blast(c.n_app, n_queries=24, db_mb=64, per_query_s=2.0)

    n0 = compile_count()
    t0 = time.monotonic()
    cold_evals = explore(wf, cands, st, verify_top_k=3, engine=eng,
                         compile_cache=cache)
    cold = time.monotonic() - t0
    cold_compiles = compile_count() - n0

    n1 = compile_count()
    t0 = time.monotonic()
    warm_evals = explore(wf, cands, st, verify_top_k=3, engine=eng,
                         compile_cache=cache)
    warm = time.monotonic() - t0
    warm_compiles = compile_count() - n1

    assert warm_compiles == 0, \
        f"warm sweep ran compile_workflow {warm_compiles} times"
    assert np.array_equal([e.makespan for e in cold_evals],
                          [e.makespan for e in warm_evals]), \
        "warm sweep results differ from cold sweep"

    # isolated DAG-construction phase (fresh cache, no simulation): the
    # Python cost the cache actually removes, without the sim wall time
    # that dominates end-to-end numbers
    c2 = CompileCache()
    t0 = time.monotonic()
    ops_cold = c2.compile_grid(wf, cands)
    dag_cold = time.monotonic() - t0
    t0 = time.monotonic()
    ops_warm = c2.compile_grid(wf, cands)
    dag_warm = time.monotonic() - t0
    assert all(a is b for a, b in zip(ops_cold, ops_warm))

    s = cache.stats
    return [
        Row("sweepcompile/cold_s", cold,
            f"{len(cands)} candidates, {s.grid_classes // 2} classes, "
            f"{cold_compiles} compile_workflow calls"),
        Row("sweepcompile/warm_s", warm,
            f"compile_workflow calls={warm_compiles} dag_hits={s.hits}"),
        Row("sweepcompile/speedup_x", cold / max(warm, 1e-9),
            f"zero_warm_compiles={warm_compiles == 0} "
            f"dedup_shared={s.dedup_shared // 2}"),
        Row("sweepcompile/dag_cold_s", dag_cold,
            f"{c2.stats.misses} compiles"),
        Row("sweepcompile/dag_warm_s", dag_warm, "all cache hits"),
        Row("sweepcompile/dag_speedup_x", dag_cold / max(dag_warm, 1e-9),
            "DAG-construction phase only"),
    ]


def sweep_shard() -> List[Row]:
    """Single-device vs device-sharded engine over one large grid.

    Both engines sweep the identical candidate list; results are
    asserted element-wise identical (the tests/test_shard.py property at
    benchmark scale). Timings are warm — each engine first pays its XLA
    compiles, then the sweep is timed alone — so the number isolates
    execution scaling, not compilation. The acceptance target: >2x
    throughput on a >=256-candidate grid with 8 forced host devices.
    """
    st = PAPER_RAMDISK
    n_dev = shard_count(resolve_mesh(0))
    cands = grid(n_nodes=[12, 14, 16, 18, 20, 22],
                 chunk_sizes=[256 * 1024, 512 * 1024, 1 * MB])
    assert len(cands) >= 256, f"grid too small: {len(cands)}"
    wf = lambda c: W.blast(c.n_app, n_queries=24, db_mb=64, per_query_s=2.0)
    wfs = [wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]
    shared_dags = CompileCache()                 # DAGs shared, engines not

    results = {}
    times = {}
    for name, sess in [
            ("single", SweepSession(compile_cache=shared_dags)),
            ("sharded", SweepSession(ShardedBackend(0),
                                     compile_cache=shared_dags))]:
        with sess:
            run = sess.prepare(wfs, cfgs, st=st)
            run.simulate()                       # pay every bucket compile
            t0 = time.monotonic()
            results[name] = run.simulate()
            times[name] = time.monotonic() - t0
            assert sess.stats.misses == sess.stats.hits  # warm: all hits
    assert np.array_equal(results["single"], results["sharded"]), \
        "sharded sweep results differ from single-device sweep"

    thru = {k: len(cands) / v for k, v in times.items()}
    speedup = times["single"] / max(times["sharded"], 1e-9)
    return [
        Row("sweepshard/single_dev_s", times["single"],
            f"{len(cands)} candidates, {thru['single']:.1f} cand/s"),
        Row("sweepshard/sharded_s", times["sharded"],
            f"{n_dev} shards, {thru['sharded']:.1f} cand/s"),
        Row("sweepshard/speedup_x", speedup,
            f"devices={n_dev} bit_identical=True "
            f"target_gt2x={'met' if speedup > 2 else 'n/a' if n_dev == 1 else 'MISSED'}"),
    ]


def sweep_trace() -> List[Row]:
    """Trace front-end end-to-end: fixture ingestion accuracy + a
    multi-workflow family sweep through the structural-dedup path.

    Part 1 ingests the shipped Montage-like and BLAST-like JSON fixtures
    and checks scan-mode against exact-mode on one deployment — the
    fixtures must sit within the sweep subsystem's documented scan
    tolerance (±10%; measured ≲1%).

    Part 2 generates a 16-member family (8 distinct structures — the
    recurrence real archives show) and sweeps it against a 16-candidate
    grid with `explore_many`, counter-asserting that structural dedup
    compiles STRICTLY fewer DAGs than family-size x grid-size, then
    times a warm repeat (zero `compile_workflow` executions).
    """
    st = PAPER_RAMDISK
    rows: List[Row] = []

    # -- part 1: fixture ingest, scan vs exact --------------------------------
    pred = Predictor(st, compile_cache=CompileCache())
    cfg = grid(n_nodes=[9], chunk_sizes=[1 * MB],
               partitions=[(4, 4)])[0].to_config()
    for fixture in ("montage_small.json", "blast_small.json"):
        wf = to_workflow(load_trace(TRACES_DIR / fixture))
        exact = pred.predict(wf, cfg, backend="exact").makespan
        scan = pred.predict(wf, cfg, backend="scan").makespan
        dev = abs(scan - exact) / exact * 100
        assert dev <= 10.0, f"{fixture}: scan {dev:.2f}% off exact"
        rows.append(Row(f"sweeptrace/{fixture.split('_')[0]}_dev_pct", dev,
                        f"scan={scan:.3f}s exact={exact:.3f}s "
                        f"tasks={len(wf.tasks)} within_10pct=True"))

    # -- part 2: generated family x grid, one batched run ---------------------
    n_members, n_structures = 16, 8
    fam = generate_family(
        GenSpec(family="iterative", depth=2, width=4, mean_mb=4,
                sigma=0.6, runtime_s=0.25),
        n_members, seed=11, n_structures=n_structures)
    wfs = [to_workflow(t) for t in fam]
    cands = grid(n_nodes=[10], chunk_sizes=[256 * 1024, 1 * MB])
    n_pairs = len(wfs) * len(cands)
    assert n_members >= 16 and n_pairs >= 16 * len(cands)

    eng = SweepEngine()
    cache = CompileCache()
    n0 = compile_count()
    t0 = time.monotonic()
    groups = explore_many(wfs, cands, st, verify_top_k=1, engine=eng,
                          compile_cache=cache)
    cold = time.monotonic() - t0
    compiles = compile_count() - n0
    assert compiles < n_pairs, \
        f"dedup failed: {compiles} compiles for {n_pairs} pairs"
    # the post-verify re-sort may rank an unverified scan estimate first
    # when exact correction exceeds the scan gap; assert each group got
    # its exact pass, not that the winner kept its rank
    assert all(any(e.verified for e in g) for g in groups)

    n1 = compile_count()
    t0 = time.monotonic()
    warm_groups = explore_many(wfs, cands, st, verify_top_k=1, engine=eng,
                               compile_cache=cache)
    warm = time.monotonic() - t0
    assert compile_count() - n1 == 0, "warm family sweep recompiled DAGs"
    assert np.array_equal([e.makespan for g in groups for e in g],
                          [e.makespan for g in warm_groups for e in g])

    rows += [
        Row("sweeptrace/family_cold_s", cold,
            f"{n_members} members x {len(cands)} candidates = {n_pairs} "
            f"pairs, {compiles} DAG compiles"),
        Row("sweeptrace/family_warm_s", warm,
            "zero compile_workflow calls, bit-identical"),
        Row("sweeptrace/dedup_ratio_x", n_pairs / max(compiles, 1),
            f"classes={cache.stats.grid_classes // 2} "
            f"shared={cache.stats.dedup_shared // 2} "
            f"strictly_fewer={compiles < n_pairs}"),
    ]
    return rows


def sweep_mp() -> List[Row]:
    """Multi-process host fan-out on a trace-family sweep (2 workers).

    Hard-asserted properties (the PR 5 acceptance):
      * the fleet's output is bit-identical to the single-process sweep;
      * per-worker `compile_workflow` counts sum to the deduped
        structural-class count (classes are partitioned whole; the
        verify round disk-hits the shared cache instead of recompiling);
      * a warm fleet repeat performs ZERO compiles anywhere.

    Timings report cold single vs cold fleet (including pool spawn and
    each worker's own XLA executable compiles — the duplicated fixed
    cost) plus the warm repeat. The speedup marker is honest about host
    width: workers pin XLA to one core each, so on hosts with < 4 cores
    the single process's intra-op threading already saturates the
    machine and the fan-out has nothing left to win — the target is
    scored only on >= 4 cores.
    """
    st = PAPER_RAMDISK
    n_workers = 2
    n_members, n_structures = 24, 12
    fam = generate_family(
        GenSpec(family="fan_out", depth=2, width=6, mean_mb=4, sigma=0.6,
                runtime_s=0.25),
        n_members, seed=5, n_structures=n_structures)
    wfs = [to_workflow(t) for t in fam]
    cands = grid(n_nodes=[10], chunk_sizes=[256 * 1024, 1 * MB])
    n_pairs = len(wfs) * len(cands)

    with SweepSession(compile_cache=CompileCache(max_entries=8192)) as single:
        t0 = time.monotonic()
        base = explore_many(wfs, cands, st, verify_top_k=1, session=single)
        t_single = time.monotonic() - t0

    # the fleet session owns its pool (lazily spawned on first dispatch),
    # so the fleet is memory-cold by construction — no shutdown_pools()
    # sweep of the process-wide registry needed. The per-item deadline is
    # generous slack, not a tuning: it exercises the submit-anchored
    # deadline plumbing without ever firing on a healthy host.
    with tempfile.TemporaryDirectory() as tmp, \
            SweepSession(MultiprocBackend(n_workers, item_timeout_s=300.0),
                         cache_dir=tmp) as sess:
        n0 = compile_count()
        t0 = time.monotonic()
        fleet = explore_many(wfs, cands, st, verify_top_k=1, session=sess)
        t_fleet = time.monotonic() - t0
        assert compile_count() == n0, "parent process compiled DAGs"
        assert sess.live_pools() == 1, "fleet did not run on the session pool"
        per_worker = dict(sess.compile_stats.worker_compiles)
        n_classes = sess.compile_stats.grid_classes
        # worker-counter asserts stand down once a late result was
        # dropped: that worker's counter rollup was discarded with its
        # values, and it may still have been writing the shared disk
        # cache when the parent moved on (CacheStats.mp_late_drops)
        clean = sess.stats.mp_late_drops == 0
        if clean:
            assert sess.stats.mp_fallbacks == 0, "a worker died mid-sweep"
            assert sum(per_worker.values()) == n_classes, (
                f"fleet compiles {per_worker} do not sum to the "
                f"{n_classes} structural classes")
        assert all(
            np.array_equal([e.makespan for e in g1], [e.makespan for e in g2])
            for g1, g2 in zip(base, fleet)), \
            "fleet sweep results differ from single-process sweep"

        t0 = time.monotonic()
        warm = explore_many(wfs, cands, st, verify_top_k=1, session=sess)
        t_warm = time.monotonic() - t0
        clean = clean and sess.stats.mp_late_drops == 0
        if clean:
            assert sum(sess.compile_stats.worker_compiles.values()) \
                == n_classes, "warm fleet repeat recompiled DAGs in a worker"
            assert compile_count() == n0, "warm fleet repeat compiled in parent"
        assert all(
            np.array_equal([e.makespan for e in g1], [e.makespan for e in g2])
            for g1, g2 in zip(base, warm))
        late = sess.stats.mp_late_drops

    speedup = t_single / max(t_fleet, 1e-9)
    ncpu = os.cpu_count() or 1
    target = ("met" if speedup > 1
              else f"n/a ({ncpu} cores)" if ncpu < 4 else "MISSED")
    counts = " ".join(f"{w}:{n}" for w, n in sorted(per_worker.items()))
    return [
        Row("sweepmp/single_cold_s", t_single,
            f"{n_pairs} pairs, {n_classes} classes, one process"),
        Row("sweepmp/fleet_cold_s", t_fleet,
            f"{n_workers} workers incl. spawn, compiles {counts} "
            f"(sum={n_classes}) late_drops={late}"),
        Row("sweepmp/fleet_warm_s", t_warm,
            "zero compiles anywhere, bit-identical" if late == 0
            else f"bit-identical; {late} late drops, counters stood down"),
        Row("sweepmp/speedup_x", speedup,
            f"bit_identical=True workers={n_workers} target_gt1x={target}"),
    ]


def sweep_faults() -> List[Row]:
    """Fault-axis sweep (docs/faults.md): the Montage fixture on spinning
    disks crossed with a degraded-disk and a mid-run-kill scenario.

    Hard-asserted properties (the PR 7 acceptance):
      * under the degraded-disk scenario the sweep selects replication=2
        (degradation-aware read steering shields readers from the sick
        disk), while the healthy subset of the SAME run still picks
        replication=1 — replication earns its cost only when the fault
        it exists for is on the table;
      * under the kill scenario every replication=1 row FAILS (no
        surviving replica) and the surviving winner has replication=2;
      * faulted candidates compile into their own executable buckets
        (`faulted` cache-key flag, counted here) and a warm repeat of
        the whole fault grid performs zero DAG compiles and returns
        bit-identical evaluations.

    Timings report the cold fault-grid sweep (DAG + XLA compiles for
    every healthy and faulted bucket) against the warm repeat.
    """
    st = PAPER_HDD
    fixed = to_workflow(load_trace(TRACES_DIR / "montage_small.json"))
    wf = lambda c: fixed
    disk = FaultScenario(degraded=(DiskDegradation(0, 16.0),), name="disk0x16")
    kill = FaultScenario(failures=(NodeFailure(0, after_tasks=3),),
                         name="kill0@3")
    base = grid(n_nodes=[9], partitions=[(4, 4)], chunk_sizes=[1 * MB],
                replications=[1, 2])
    cands = with_faults(base, (None, disk, kill))

    with SweepSession() as sess:
        n0 = compile_count()
        t0 = time.monotonic()
        evals = explore(wf, cands, st, verify_top_k=len(cands), session=sess)
        cold = time.monotonic() - t0
        compiles = compile_count() - n0
        n_faulted = sum(1 for k in sess.engine.cache_keys() if k[5])
        assert n_faulted >= 1, "no faulted executable bucket was compiled"

        n1 = compile_count()
        t0 = time.monotonic()
        warm = explore(wf, cands, st, verify_top_k=len(cands), session=sess)
        t_warm = time.monotonic() - t0
        # stand down if a late worker result was ever dropped on this
        # session (inline runs keep the counter at 0): such a worker may
        # still be writing the shared cache behind the parent's back
        if sess.stats.mp_late_drops == 0:
            assert compile_count() - n1 == 0, \
                "warm fault sweep recompiled DAGs"
        assert np.array_equal([e.makespan for e in evals],
                              [e.makespan for e in warm]), \
            "warm fault sweep results differ from cold sweep"

    by_scen = lambda f: [e for e in evals if e.candidate.faults == f]
    healthy, degraded, killed = by_scen(None), by_scen(disk), by_scen(kill)
    assert healthy[0].candidate.replication == 1, \
        "healthy sweep should not pay for replication"
    assert degraded[0].candidate.replication == 2 and not degraded[0].failed, \
        "degraded sweep failed to select replication=2"
    assert all(e.failed for e in killed if e.candidate.replication == 1), \
        "a replication=1 run survived the kill"
    assert killed[0].candidate.replication == 2 and not killed[0].failed, \
        "kill sweep winner should be a surviving replication=2 run"
    assert all(e.verified for e in evals)

    slowdown = degraded[0].makespan / healthy[0].makespan
    win = degraded[1].makespan / degraded[0].makespan
    return [
        Row("sweepfaults/cold_s", cold,
            f"{len(cands)} candidates, {compiles} DAG compiles, "
            f"{n_faulted} faulted buckets"),
        Row("sweepfaults/warm_s", t_warm,
            "zero compiles, bit-identical"),
        Row("sweepfaults/degraded_win_x", win,
            f"r2 {degraded[0].makespan:.2f}s vs r1 {degraded[1].makespan:.2f}s "
            f"under {disk.name}; healthy best r="
            f"{healthy[0].candidate.replication}"),
        Row("sweepfaults/degraded_cost_x", slowdown,
            f"best-under-fault vs healthy best "
            f"({healthy[0].makespan:.2f}s); kill survivors r=2 only"),
    ]


def sweep_kernel() -> List[Row]:
    """Fused Pallas scan kernel vs the XLA reference (docs/roofline.md).

    Hard-asserted properties (this PR's acceptance):
      * the kernel session's makespans are BIT-IDENTICAL to the XLA
        session's across the full fixture sweep, healthy and faulted
        buckets alike (both paths run the same max/add sequence, so
        this is exact equality, not a tolerance);
      * every scan bucket actually took the kernel path
        (``kernel_buckets`` > 0, ``kernel_fallbacks`` == 0), and the
        XLA session compiled zero kernel buckets.

    Timings are warm (each session pays its bucket compiles first). The
    kernel runs only in Pallas *interpret* mode on the CPU — a
    correctness harness every CI leg exercises, not a fast path; Mosaic
    refuses it for TPU — so no speed target is scored. The ERT rows
    (`roofline.sweep_ert`) ride along so the per-bucket bytes / flops /
    achieved-fraction characterization lands in the same JSON artifact.
    """
    from .roofline import sweep_ert

    st = PAPER_RAMDISK
    wf = to_workflow(load_trace(TRACES_DIR / "montage_small.json"))
    disk = FaultScenario(degraded=(DiskDegradation(0, 8.0),), name="disk0x8")
    cands = with_faults(grid(n_nodes=[7, 9], chunk_sizes=[512 * 1024, 1 * MB]),
                        (None, disk))
    wfs = [wf] * len(cands)
    cfgs = [c.to_config() for c in cands]
    shared_dags = CompileCache()

    results, times, kstats = {}, {}, {}
    for name in ("xla", "pallas"):
        with SweepSession(compile_cache=shared_dags, sim_engine=name) as sess:
            run = sess.prepare(wfs, cfgs, st=st)
            run.simulate()                       # pay every bucket compile
            t0 = time.monotonic()
            results[name] = run.simulate()
            times[name] = time.monotonic() - t0
            kstats[name] = (sess.stats.kernel_buckets,
                            sess.stats.kernel_fallbacks)
    assert np.array_equal(results["xla"], results["pallas"]), \
        "kernel sweep results differ from the XLA sweep"
    kb, kf = kstats["pallas"]
    assert kb > 0, "no bucket took the kernel path"
    assert kf == 0, f"kernel path fell back {kf} times"
    assert kstats["xla"][0] == 0, "XLA session compiled kernel buckets"

    speedup = times["xla"] / max(times["pallas"], 1e-9)
    return [
        Row("sweepkernel/xla_s", times["xla"],
            f"{len(cands)} candidates incl. faulted, warm"),
        Row("sweepkernel/pallas_s", times["pallas"],
            f"kernel_buckets={kb} fallbacks={kf} mode=interpret"),
        Row("sweepkernel/speedup_x", speedup,
            "bit_identical=True target_gt1x=n/a (interpret mode)"),
    ] + sweep_ert()


def sweep_scenarios() -> List[Row]:
    st = PAPER_RAMDISK
    rows: List[Row] = []
    for name, wf in [
            ("scatter_gather", lambda c: W.scatter_gather(
                c.n_app, in_mb=32, shard_mb=8, out_mb=2)),
            ("map_reduce_shuffle", lambda c: W.map_reduce_shuffle(
                c.n_app, rounds=2, in_mb=16, part_mb=2, out_mb=8))]:
        eng = SweepEngine()
        cands = grid(n_nodes=[10], chunk_sizes=[256 * 1024, 1 * MB])
        evals = explore(wf, cands, st, verify_top_k=3, engine=eng)
        best = evals[0]
        ref = ref_sim.simulate(
            compile_workflow(wf(best.candidate), best.candidate.to_config()),
            st).makespan
        rows.append(Row(
            f"sweepscenarios/{name}_best_s", best.makespan,
            f"app={best.candidate.n_app} sto={best.candidate.n_storage} "
            f"ref={ref:.3f}s verified={best.verified} "
            f"exact_batches={eng.stats.exact_batch_calls}"))
    return rows


def sweep_obs() -> List[Row]:
    """Observability end-to-end (docs/observability.md): one profiled
    Montage-fixture sweep across ALL THREE backends sharing one tracer,
    exported as a single Perfetto-loadable trace.

    Hard-asserted properties (the PR 9 acceptance):
      * inline, device-sharded, and multiproc sweeps of the same grid
        return bit-identical evaluations — with the tracer ON;
      * the trace holds wall-clock spans from every pipeline phase and
        from the multiproc worker processes (their own tracks, disjoint
        from "host");
      * the best candidate's simulated `Timeline` yields a contiguous
        critical path whose duration equals the reported makespan to
        float tolerance;
      * a traced sweep against a fresh session is *bit-identical* to an
        untraced one — same makespans, same compile count, same engine
        batch/miss counters (tracing changes observation, not behaviour).

    Writes the combined trace (spans + best-candidate timeline + metrics
    snapshot) to ``$REPRO_TRACE_OUT`` (default ``sweep-trace.json`` in
    the working directory) — the artifact CI uploads per push.
    """
    from repro.obs import (Tracer, metrics_snapshot, spans_to_events,
                           timeline_to_events, write_trace)
    from repro.core.sweep.backends import InlineBackend

    st = PAPER_RAMDISK
    fixed = to_workflow(load_trace(TRACES_DIR / "montage_small.json"))
    wf = lambda c: fixed
    cands = grid(n_nodes=[9], partitions=[(4, 4)],
                 chunk_sizes=[256 * 1024, 1 * MB])

    tracer = Tracer()
    results = {}
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory() as tmp:
        # one shared disk cache: the inline sweep compiles, the sharded
        # and multiproc sweeps (and the mp workers) disk-hit it
        for name, backend in (("inline", InlineBackend()),
                              ("sharded", ShardedBackend(0)),
                              ("mp", MultiprocBackend(2))):
            with SweepSession(backend, cache_dir=tmp,
                              tracer=tracer) as sess:
                results[name] = explore(wf, cands, st, verify_top_k=2,
                                        timeline_top_k=1, session=sess)
            metrics = metrics_snapshot(sess)   # stats survive close()
    t_traced = time.monotonic() - t0

    base = [e.makespan for e in results["inline"]]
    for name in ("sharded", "mp"):
        assert np.array_equal(base, [e.makespan for e in results[name]]), \
            f"{name} backend diverged from inline under tracing"

    phases = {s.phase for s in tracer.spans()}
    for ph in ("compile", "host-prep", "device-sim", "exact-verify",
               "dispatch", "merge"):
        assert ph in phases, f"no '{ph}' span was recorded"
    tracks = tracer.tracks()
    workers = [t for t in tracks if t != "host"]
    assert "host" in tracks and workers, \
        f"expected host + worker tracks, got {tracks}"

    best = results["inline"][0]
    tl = best.timeline
    assert tl is not None, "timeline_top_k=1 attached no timeline"
    cp = tl.critical_path_duration()
    cp_dev = abs(cp - tl.makespan) / max(tl.makespan, 1e-12)
    assert cp_dev <= 1e-6, \
        f"critical path {cp!r} != makespan {tl.makespan!r}"
    assert abs(tl.makespan - best.makespan) <= 1e-9 * best.makespan, \
        "timeline re-simulation diverged from the sweep's makespan"

    # -- tracer-off differential: observation must not change behaviour -----
    runs = {}
    for label, tr in (("on", Tracer()), ("off", None)):
        n0 = compile_count()
        with SweepSession(InlineBackend(), tracer=tr) as sess:
            evals = explore(wf, cands, st, verify_top_k=2, session=sess)
            runs[label] = ([e.makespan for e in evals],
                           compile_count() - n0,
                           sess.stats.batch_calls, sess.stats.misses)
    (ms_on, comp_on, bc_on, miss_on) = runs["on"]
    (ms_off, comp_off, bc_off, miss_off) = runs["off"]
    assert np.array_equal(ms_on, ms_off), "tracing changed sweep results"
    assert comp_on == comp_off, "tracing changed the compile count"
    assert (bc_on, miss_on) == (bc_off, miss_off), \
        "tracing changed engine batch/miss counters"

    out = os.environ.get("REPRO_TRACE_OUT", "sweep-trace.json")
    events = spans_to_events(tracer.spans()) \
        + timeline_to_events(tl, label="best candidate (simulated)")
    path = write_trace(out, events, metrics=metrics,
                       meta={"benchmark": "sweepobs",
                             "workers": sorted(workers)})
    n_spans = len(tracer.spans())
    return [
        Row("sweepobs/traced_sweep_s", t_traced,
            f"3 backends bit-identical, {n_spans} spans, "
            f"tracks={','.join(tracks)}"),
        Row("sweepobs/critical_path_dev_pct", cp_dev * 100,
            f"cp={cp:.6f}s makespan={tl.makespan:.6f}s "
            f"path_len={len(tl.critical_path())}"),
        Row("sweepobs/tracer_off_delta", 0.0,
            f"bit_identical=True compiles {comp_on}=={comp_off} "
            f"batches {bc_on}=={bc_off} misses {miss_on}=={miss_off}"),
        Row("sweepobs/trace_bytes", float(path.stat().st_size),
            f"perfetto json at {path}"),
    ]
