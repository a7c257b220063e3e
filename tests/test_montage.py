"""The `montage` family of `repro.core.trace.generate`: the published
structure at its real counts, determinism under the seed, the program's
makespans against the benchmark's independent copy of the workflow and
its plain reference, and the host-order-loop span and barrier-op
counter that the Montage what-if cell reads beside it."""
import dataclasses
import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro.core import (PAPER_RAMDISK, Candidate, InlineBackend,
                        ServiceTimes, SweepSession, compile_workflow, explore,
                        partitioned_config)
from repro.core import jax_sim
from repro.core import workloads as W
from repro.core.compile import CLS_NONE
from repro.core.sweep import CompileCache, SweepEngine
from repro.core.trace import GenSpec, generate_workflow, to_workflow
from repro.obs import Tracer

ROOT = Path(__file__).resolve().parents[1]
CONFIG = json.loads((ROOT / "bench/configs/montage-4deg.json").read_text())

# Juve et al., FGCS 29(3), 2013: the 8-degree 2MASS run
PUBLISHED = {"mProjectPP": 2102, "mDiffFit": 6172, "mConcatFit": 1,
             "mBgModel": 1, "mBackground": 2102, "mImgtbl": 17, "mAdd": 17,
             "mShrink": 16, "mJPEG": 1}

# the benchmark's own limits for the two modes: the what-if mix's
# scan_gap and the advisor mix's exact_gap. On the CPU both sides run
# IEEE f64 over the same op order, so the gaps read at rounding size;
# the limits are what a served answer on the chip is held to
SCAN_GAP = 1e-7
EXACT_GAP = 1e-8


def _bench():
    """The benchmark's family, layouts and plain reference, which import
    nothing of the program."""
    sys.path.insert(0, str(ROOT))
    try:
        from bench import generator as G
        from bench import workflows as BW
        from bench.families import montage
        from bench.reference.compile import compile_dag
        from bench.reference.sim import exact_makespan, scan_makespan
    finally:
        sys.path.remove(str(ROOT))
    return G, BW, montage, compile_dag, exact_makespan, scan_makespan


def montage(k, tiles, *, wass=False, sigma=0.0, seed=0):
    return to_workflow(generate_workflow(
        GenSpec(family="montage", width=k, tiles=tiles, sigma=sigma,
                wass=wass), seed=seed))


def rel_gap(got, want):
    return abs(got - want) / abs(want)


@pytest.mark.parametrize("k,tiles,total", [(46, 4, 10450), (23, 2, 2571)])
def test_transformation_counts(k, tiles, total):
    counts = Counter(t.stage for t in montage(k, tiles).tasks)
    assert sum(counts.values()) == total
    images, fits = k * k, 2 * k * (k - 1) + (k - 1) ** 2
    n_tiles = tiles * tiles
    assert counts == {"mProjectPP": images, "mDiffFit": fits,
                      "mConcatFit": 1, "mBgModel": 1, "mBackground": images,
                      "mImgtbl": n_tiles + 1, "mAdd": n_tiles + 1,
                      "mShrink": n_tiles, "mJPEG": 1}
    if k == 46:
        assert (counts["mProjectPP"], counts["mDiffFit"]) == (2116, 6165)
        for kind, n in PUBLISHED.items():
            assert abs(counts[kind] - n) <= 0.01 * n, kind
        assert abs(total - 10429) <= 0.01 * 10429


def test_tiles_partition_the_images_and_fan_in():
    wf = montage(23, 2)
    by_stage = {}
    for t in wf.tasks:
        by_stage.setdefault(t.stage, []).append(t)
    [concat] = by_stage["mConcatFit"]
    assert len(concat.inputs) == 1496
    tile_adds = by_stage["mAdd"][:-1]
    assert sorted(len(t.inputs) - 1 for t in tile_adds) == [121, 132, 132,
                                                            144]
    assert all(t.client is None for t in wf.tasks)


@pytest.mark.parametrize("wass", [False, True])
def test_same_seed_same_fingerprint(wass):
    a = montage(5, 2, wass=wass, sigma=0.5, seed=11)
    b = montage(5, 2, wass=wass, sigma=0.5, seed=11)
    c = montage(5, 2, wass=wass, sigma=0.5, seed=12)
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()
    # sigma 0 draws nothing: every seed gives the published sizes
    assert montage(5, 2, wass=wass, seed=1).fingerprint() \
        == montage(5, 2, wass=wass, seed=2).fingerprint()


def test_wass_collocates_fits_and_each_tiles_images():
    wf = montage(4, 2, wass=True)
    groups = {}
    for t in wf.tasks:
        for f, a in t.file_attrs.items():
            assert a.placement.value == "collocate"
            groups.setdefault(a.collocate_group, []).append((t.stage, f))
    assert {s for s, _ in groups.pop("fits")} == {"mDiffFit"}
    assert sorted(len(v) for v in groups.values()) == [4, 4, 4, 4]
    assert all(s == "mBackground" for v in groups.values() for s, _ in v)
    assert not any(t.file_attrs for t in montage(4, 2).tasks)


@pytest.mark.parametrize("wass", [False, True], ids=["dss", "wass"])
def test_program_matches_the_benchmarks_reference(wass):
    """The generated Montage through `to_workflow` and `explore` (scan,
    then exact on every candidate) against the benchmark's own builder
    through its plain compiler and simulators, on two splits at both of
    the configuration's chunk sizes."""
    G, BW, family, compile_dag, exact_makespan, scan_makespan = _bench()
    cfg = dict(CONFIG, grid_side=3, tiles_per_side=2)
    n_nodes = 6
    lays = [BW.layout(a, s, chunk_size=ck * 1024)
            for a, s in ((3, 2), (2, 3)) for ck in cfg["chunk_kb"]]
    cands = [BW.to_candidate(lay, n_nodes) for lay in lays]
    wf = montage(3, 2, wass=wass)
    plain = family.build(cfg, {"wass": wass}, 3, 0)
    st = G.service_times(CONFIG["service_times"]["hdd"])
    evals = explore(lambda c: wf, cands, ServiceTimes(**st),
                    verify_top_k=len(cands))
    assert sorted(e.index for e in evals) == list(range(len(cands)))
    for e in evals:
        dag = compile_dag(plain, lays[e.index])
        assert e.verified
        assert rel_gap(e.scan_makespan, scan_makespan(dag, st)) <= SCAN_GAP
        assert rel_gap(e.makespan, exact_makespan(dag, st)) <= EXACT_GAP
    # WASS moves the answer: the hints reach the placement
    other = explore(lambda c: montage(3, 2, wass=not wass), cands,
                    ServiceTimes(**st), verify_top_k=0)
    assert {e.index: e.scan_makespan for e in evals} \
        != {e.index: e.scan_makespan for e in other}


def test_order_loop_span_fires_once_per_loop_row():
    """Loop-ordered rows each open one ``order_loop|host-order-loop``
    span inside their ``order|host-order`` span; level-ordered rows open
    none."""
    deep = W.pipeline(1, stage_mb=(1, 1, 1, 1))
    dags = [
        # wide enough for the level pass
        W.blast(3, n_queries=12, db_mb=64),
        # under LEVEL_MIN_OPS: the loop
        deep,
    ]
    cfg = partitioned_config(3, 2, chunk_size=256 * 1024)
    ops = [compile_workflow(wf, cfg) for wf in dags]
    assert [jax_sim.dag_levels(o).vectorized for o in ops] == [True, False]
    tr = Tracer()
    eng = SweepEngine(sim_engine="xla", tracer=tr)
    st = PAPER_RAMDISK
    # the repeated row hits the row cache: no second ordering
    eng.simulate_batch(ops + [ops[1]], [st, st, st])
    spans = tr.spans()
    loops = [s for s in spans if s.phase == "host-order-loop"]
    orders = [s for s in spans if s.phase == "host-order"]
    assert [s.name for s in loops] == ["order_loop"] * eng.stats.loop_rows
    assert (eng.stats.level_rows, eng.stats.loop_rows) == (1, 1)
    assert len(orders) == 2
    assert all(any(o.start <= s.start and s.end <= o.end + 1e-9
                   for o in orders) for s in loops)
    # new service times: one more row of each kind, one more loop span
    slow = dataclasses.replace(st, storage=st.storage * 2)
    eng.simulate_batch(ops, [slow, slow])
    assert eng.stats.loop_rows == 2
    assert sum(s.phase == "host-order-loop" for s in tr.spans()) == 2


def test_barrier_ops_count_the_zero_cost_ops():
    cache = CompileCache()
    cands = [partitioned_config(a, s, chunk_size=ck)
             for a, s in ((3, 2), (2, 3)) for ck in (1 << 20, 4 << 20)]

    class Cand:
        def __init__(self, cfg):
            self.cfg = cfg

        def to_config(self):
            return self.cfg

    wf = montage(3, 2)
    out = cache.compile_grid(lambda c: wf, [Cand(c) for c in cands])
    counts = [int(np.count_nonzero((o.res == 0) & (o.cls == CLS_NONE)))
              for o in out]
    assert [o.n_barriers for o in out] == counts
    assert cache.stats.barrier_ops == sum(counts) > 0
    # a warm grid compiles nothing and counts nothing more
    cache.compile_grid(lambda c: wf, [Cand(c) for c in cands])
    assert cache.stats.barrier_ops == sum(counts)


def test_dead_ops_are_not_barriers():
    """A lost chunk compiles to an unservable op on the same dummy
    resource and class: it is not counted."""
    from repro.core import FaultScenario, NodeFailure
    cfg = partitioned_config(3, 2, chunk_size=1 << 20, faults=FaultScenario(
        failures=(NodeFailure(0, after_tasks=20),)))
    ops = compile_workflow(montage(3, 2), cfg)
    assert ops.dead is not None and ops.dead.sum() > 0
    none = int(np.count_nonzero((ops.res == 0) & (ops.cls == CLS_NONE)))
    assert ops.n_barriers == none - int(ops.dead.sum())


def test_barrier_count_survives_the_disk_cache(tmp_path):
    wf = montage(3, 2)
    cfg = partitioned_config(3, 2, chunk_size=1 << 20)
    fresh = CompileCache(path=tmp_path).get(wf, cfg)
    loaded = CompileCache(path=tmp_path).get(wf, cfg)
    assert loaded is not fresh
    assert loaded.n_barriers == fresh.n_barriers > 0


def test_montage_runs_through_a_session():
    """The normal entry: a `SweepSession` sweep of the generated
    workflow, scan only, as the what-if cell asks."""
    wf = montage(3, 2, wass=True)
    cands = [Candidate(n_nodes=6, n_app=a, n_storage=5 - a,
                       chunk_size=1 << 20) for a in (2, 3)]
    with SweepSession(InlineBackend()) as sess:
        evals = explore(lambda c: wf, cands, PAPER_RAMDISK,
                        verify_top_k=0, session=sess)
        assert sess.compile_stats.barrier_ops > 0
    assert [e.makespan for e in evals] == sorted(e.makespan for e in evals)
    assert all(e.makespan > 0 and not e.verified for e in evals)
