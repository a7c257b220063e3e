"""`jax_sim.scan_order` against the per-op loop it grew out of, bit for
bit, on every path it takes, and the sweep engine's per-DAG level cache.

The loop below is the oracle: the estimated-start pass exactly as
`scan_order` ran it before the level-synchronous pass. Every path the
program takes — the level pass over a `dag_levels` partition, the list
loop for small, deep or non-topological DAGs — must return the same
permutation, since the scan-mode makespans follow from it.
"""
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import (KB, MB, PAPER_HDD, PAPER_RAMDISK, DiskDegradation,
                        FaultScenario, NodeFailure, ServiceTimes,
                        compile_workflow, partitioned_config)
from repro.core import jax_sim
from repro.core import workloads as W
from repro.core.compile import MAXD, MicroOps
from repro.core.ref_sim import durations
from repro.core.sweep import SweepEngine

ROOT = Path(__file__).resolve().parents[1]
# the what-if benchmark's slow-network, HDD corner: other ties than RAMdisk
WHATIF = dataclasses.replace(PAPER_HDD, net_remote=PAPER_HDD.net_remote * 2,
                             net_local=PAPER_HDD.net_local * 2)


def oracle_order(ops: MicroOps, st: ServiceTimes) -> np.ndarray:
    dur = durations(ops, st) + ops.nlat * st.net_latency
    n = ops.n_ops
    est_end = np.zeros(n)
    est_start = np.zeros(n)
    deps, ends = ops.deps, est_end
    for i in range(n):
        s = 0.0
        for d in deps[i]:
            if d >= 0 and ends[d] > s:
                s = ends[d]
        est_start[i] = s
        ends[i] = s + dur[i]
    return np.argsort(est_start, kind="stable").astype(np.int32)


def forced_levels(ops: MicroOps, monkeypatch) -> jax_sim.DagLevels:
    """The level partition whatever the DAG's size and shape."""
    with monkeypatch.context() as m:
        m.setattr(jax_sim, "LEVEL_MIN_OPS", 0)
        m.setattr(jax_sim, "LEVEL_MIN_WIDTH", 0)
        return jax_sim.dag_levels(ops)


def assert_partition(ops: MicroOps, lv: jax_sim.DagLevels) -> None:
    """Every op in one level, every dep in an earlier level."""
    n = ops.n_ops
    assert sorted(lv.order.tolist()) == list(range(n))
    assert lv.bounds[0] == 0 and lv.bounds[-1] == n
    assert lv.deps.shape == (MAXD, n)
    for a, b in zip(lv.bounds[:-1], lv.bounds[1:]):
        d = lv.deps[:, a:b]
        assert ((d < a) | (d == n)).all()
    want = np.where(ops.deps >= 0, ops.deps, n)[lv.order].T
    got = np.append(lv.order, n)[lv.deps]
    np.testing.assert_array_equal(got, want)


def assert_every_path_matches(ops: MicroOps, monkeypatch,
                              sts=(PAPER_RAMDISK, WHATIF)) -> None:
    lv = forced_levels(ops, monkeypatch)
    assert lv.vectorized
    assert_partition(ops, lv)
    for st in sts:
        want = oracle_order(ops, st)
        for levels in (None, lv, jax_sim.SEQUENTIAL):
            got = jax_sim.scan_order(ops, st, levels)
            assert got.dtype == np.int32
            np.testing.assert_array_equal(got, want)


CFG = dict(chunk_size=256 * KB)

FAMILIES = {
    "pipeline": lambda: W.pipeline(3, stage_mb=(4, 8, 4, 1)),
    "pipeline_wass": lambda: W.pipeline(3, wass=True, stage_mb=(4, 8, 4, 1)),
    "reduce": lambda: W.reduce_(3, in_mb=4, mid_mb=4, out_mb=8),
    "reduce_wass": lambda: W.reduce_(3, wass=True, in_mb=4, mid_mb=4,
                                     out_mb=8),
    "broadcast": lambda: W.broadcast(3, file_mb=4, replication=2),
    "blast": lambda: W.blast(3, n_queries=12, db_mb=8),
    "stripe_sweep": lambda: W.stripe_sweep_workload(3, file_mb=4),
    "scatter_gather": lambda: W.scatter_gather(3, in_mb=6, shard_mb=2,
                                               out_mb=1),
    "map_reduce_shuffle": lambda: W.map_reduce_shuffle(3, 2, rounds=2,
                                                       in_mb=4, part_mb=1,
                                                       out_mb=2),
    "checkpoint_write": lambda: W.checkpoint_write(3, 3 * MB),
    "checkpoint_restore": lambda: W.checkpoint_restore(3, 3 * MB,
                                                       replication=2,
                                                       full_restore=True),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scan_order_matches_the_loop_on_every_family(family, monkeypatch):
    ops = compile_workflow(FAMILIES[family](),
                           partitioned_config(3, 2, replication=2, **CFG))
    assert_every_path_matches(ops, monkeypatch)


def _bench_dags(config: str, mix: str, n_req: int):
    """The benchmark cell's DAGs at its configuration's rehearse sizes."""
    sys.path.insert(0, str(ROOT))
    try:
        from bench import generator as G
        from bench import workflows as BW
    finally:
        sys.path.remove(str(ROOT))
    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    cfg.update(cfg["rehearse"])
    gen = G.Generator(cfg, json.loads(
        (ROOT / "bench/traffic" / f"{mix}.json").read_text()), 2**31 + 12345, 51)
    out = []
    for _ in range(n_req):
        _, req = gen.next()
        for wf, lay in zip(req.workflows, req.layouts):
            cand = BW.to_candidate(lay, cfg["n_nodes"])
            out.append((compile_workflow(BW.to_program(wf), cand.to_config()),
                        ServiceTimes(**req.st)))
    return out


@pytest.mark.parametrize("config,mix,n_req", [("blast-sc1", "whatif", 1),
                                              ("synth-micro", "scan", 5)])
def test_scan_order_matches_the_loop_on_bench_dags(config, mix, n_req,
                                                   monkeypatch):
    dags = _bench_dags(config, mix, n_req)
    assert dags
    for ops, st in dags:
        assert_every_path_matches(ops, monkeypatch, sts=(st, PAPER_RAMDISK))


@pytest.mark.parametrize("scenario", [
    FaultScenario(degraded=(DiskDegradation(0, 8.0),), name="disk0x8"),
    FaultScenario(failures=(NodeFailure(0, after_tasks=3),), name="kill0@3"),
], ids=lambda s: s.name)
def test_scan_order_matches_the_loop_on_a_faulted_dag(scenario, monkeypatch):
    ops = compile_workflow(
        W.pipeline(3, stage_mb=(4, 8, 4, 1)),
        partitioned_config(3, 3, faults=scenario, **CFG))
    assert jax_sim.faulted(ops)
    assert_every_path_matches(ops, monkeypatch)


def _chain(n: int) -> MicroOps:
    """A deep DAG: every op waits for the one before it (levels = N)."""
    ops = compile_workflow(W.pipeline(1, stage_mb=(1, 1, 1, 1)),
                           partitioned_config(1, 1, chunk_size=MB))
    reps = -(-n // ops.n_ops)

    def tile(a):
        return np.concatenate([a] * reps)[:n]
    deps = np.full((n, MAXD), -1, dtype=np.int32)
    deps[1:, 0] = np.arange(n - 1)
    deps[1::3, 1] = 0              # a repeated root dep on some ops
    return MicroOps(res=tile(ops.res), cls=tile(ops.cls),
                    nbytes=tile(ops.nbytes), reqs=tile(ops.reqs),
                    extra=tile(ops.extra), nlat=tile(ops.nlat), deps=deps,
                    n_resources=ops.n_resources)


def test_deep_chain_takes_the_loop_and_matches(monkeypatch):
    ops = _chain(jax_sim.LEVEL_MIN_OPS)
    # wide enough in ops, far too deep for the level pass
    assert jax_sim.dag_levels(ops) is jax_sim.SEQUENTIAL
    lv = forced_levels(ops, monkeypatch)
    assert len(lv.bounds) - 1 == ops.n_ops
    assert_every_path_matches(ops, monkeypatch)


def test_small_dag_takes_the_loop():
    ops = compile_workflow(FAMILIES["blast"](), partitioned_config(3, 2, **CFG))
    assert ops.n_ops < jax_sim.LEVEL_MIN_OPS
    assert jax_sim.dag_levels(ops) is jax_sim.SEQUENTIAL


def test_non_topological_dep_keeps_the_loop_semantics(monkeypatch):
    """A dep at or after its own op, which the compiler never emits,
    reads an end of 0, as the loop reads it: the partition declines."""
    ops = compile_workflow(FAMILIES["reduce"](), partitioned_config(3, 2, **CFG))
    deps = ops.deps.copy()
    row = next(i for i in range(ops.n_ops // 2, ops.n_ops)
               if (deps[i] < 0).any())
    deps[row, np.flatnonzero(deps[row] < 0)[0]] = ops.n_ops - 1
    deps[row + 1, -1] = row + 1                     # a self-dep
    bad = dataclasses.replace(ops, deps=deps)
    assert forced_levels(bad, monkeypatch) is jax_sim.SEQUENTIAL
    for st in (PAPER_RAMDISK, WHATIF):
        np.testing.assert_array_equal(jax_sim.scan_order(bad, st),
                                      oracle_order(bad, st))


# -- the engine's level cache ----------------------------------------------------

def _grid():
    """Two DAGs the level pass takes, one the loop takes."""
    out = [compile_workflow(W.blast(3, n_queries=12, db_mb=db),
                            partitioned_config(3, 2, chunk_size=c))
           for db, c in ((64, 256 * KB), (72, 256 * KB), (64, 4 * MB))]
    assert [o.n_ops >= jax_sim.LEVEL_MIN_OPS for o in out] == \
        [True, True, False]
    return out


def test_level_cache_builds_once_per_dag_across_service_times():
    dags = _grid()
    eng = SweepEngine(sim_engine="xla")
    sts = [PAPER_RAMDISK, WHATIF,
           dataclasses.replace(PAPER_RAMDISK,
                               storage=PAPER_RAMDISK.storage * 0.5)]
    runs = []
    for k, st in enumerate(sts):
        runs.append(eng.simulate_batch(dags, [st] * len(dags)))
        s = eng.stats
        assert s.level_misses == len(dags)
        assert s.level_hits == k * len(dags)
        assert s.row_misses == (k + 1) * len(dags)
        assert (s.level_rows, s.loop_rows) == (2 * (k + 1), k + 1)
        # same makespans as the engine-free path over the loop's order
        ref = jax_sim.simulate_batch(dags, [st] * len(dags))
        np.testing.assert_array_equal(runs[-1], ref)
    # a warm row repeats no ordering at all
    eng.simulate_batch(dags, [sts[0]] * len(dags))
    assert eng.stats.level_hits == 2 * len(dags)
    assert eng.stats.level_rows + eng.stats.loop_rows == 3 * len(dags)


def test_level_cache_cold_and_warm_give_the_loops_permutation():
    dags = _grid()
    eng = SweepEngine(sim_engine="xla")
    for st in (PAPER_RAMDISK, WHATIF):        # cold, then warm
        for ops in dags:
            np.testing.assert_array_equal(
                jax_sim.scan_order(ops, st, eng._dag_levels(ops)),
                oracle_order(ops, st))
    assert (eng.stats.level_misses, eng.stats.level_hits) == (3, 3)


def test_release_empties_the_level_cache_and_reset_zeroes_its_counters():
    dags = _grid()[1:]
    eng = SweepEngine(sim_engine="xla")
    eng.simulate_batch(dags, [PAPER_RAMDISK] * len(dags))
    # each entry pins its DAG, so a recycled id() never finds it
    assert [v[0] for v in eng._levels.values()] == dags
    eng.release()
    assert not eng._levels
    eng.simulate_batch(dags, [WHATIF] * len(dags))
    assert eng.stats.level_misses == 2 * len(dags)
    eng.stats.reset()
    assert (eng.stats.level_hits, eng.stats.level_misses,
            eng.stats.level_rows, eng.stats.loop_rows) == (0, 0, 0, 0)

