"""Tests for `repro.obs`: span tracing, simulated timelines, trace
export, and the unified metrics snapshot (docs/observability.md).

The two load-bearing contracts:

* **Observation never changes behaviour** — a traced sweep is
  bit-identical to an untraced one, counter-asserted (same compiles,
  same engine batch calls / cache misses).
* **The timeline explains the makespan** — critical-path extraction
  finds a contiguous chain from t=0 whose duration equals the reported
  makespan to float tolerance, for scan and exact modes, healthy and
  faulted runs alike.
"""
import concurrent.futures
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from repro.core import (MB, PAPER_RAMDISK, CompileCache, DiskDegradation,
                        FaultScenario, MultiprocBackend, SweepEngine,
                        SweepSession, compile_workflow, explore, grid)
from repro.core import jax_sim
from repro.core import workloads as W
from repro.core.compile import (CLS_CLIENT, CLS_CPU, CLS_MANAGER,
                                CLS_NET_LOCAL, CLS_NET_REMOTE, CLS_NONE,
                                CLS_STORAGE, compile_count)
from repro.core.sweep import multiproc
from repro.core.sweep.backends import InlineBackend
from repro.core.sweep.engine import CacheStats
from repro.core.sweep.compilecache import CompileCacheStats
from repro.obs import (NULL_TRACER, NullTracer, Tracer, metrics_snapshot,
                       resource_names, spans_to_events, stats_snapshot,
                       timeline_to_events, write_trace)
from repro.obs.export import CLASS_NAMES

ST = PAPER_RAMDISK


def small_cfg(**kw):
    from repro.core import collocated_config
    return collocated_config(5, chunk_size=256 * 1024, **kw)


# ---------------- tracer ----------------------------------------------------------

def test_tracer_records_spans_with_phase_and_meta():
    tr = Tracer()
    with tr.span("outer", phase="compile", candidates=3):
        with tr.span("inner", phase="host-prep"):
            pass
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # completion order
    outer = spans[1]
    assert outer.phase == "compile"
    assert dict(outer.meta) == {"candidates": 3}
    assert outer.track == "host"
    assert 0.0 <= spans[0].start and spans[0].dur >= 0.0
    # inner nests inside outer on the shared epoch clock
    assert spans[0].start >= outer.start
    assert spans[0].end <= outer.end + 1e-9
    tr.clear()
    assert tr.spans() == ()


def test_tracer_span_survives_exceptions():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("inside")
    assert [s.name for s in tr.spans()] == ["boom"]


def test_tracer_is_thread_safe():
    tr = Tracer()
    n, per = 8, 50

    def worker(k):
        for i in range(per):
            with tr.span(f"t{k}.{i}"):
                pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans()) == n * per


def test_null_tracer_is_inert():
    nt = NullTracer()
    with nt.span("anything", phase="x", k=1):
        pass
    assert nt.spans() == () and nt.wire_spans() == [] and nt.tracks() == ()
    assert not nt.enabled
    nt.absorb([("a", 0.0, 1.0, "", ())], offset=0.0, track="w")
    assert nt.spans() == ()
    # the module constant is the same stateless kind
    assert isinstance(NULL_TRACER, NullTracer)


def test_absorb_rebases_and_preserves_order():
    parent = Tracer()
    wire = [("b", 0.5, 0.2, "sim", (("rows", 4),)),
            ("a", 0.0, 0.4, "compile", ())]
    parent.absorb(wire, offset=10.0, track="w7")
    spans = parent.spans()
    assert [s.name for s in spans] == ["b", "a"]   # input order preserved
    assert spans[0].start == pytest.approx(10.5)
    assert spans[0].track == "w7" and spans[1].track == "w7"
    assert dict(spans[0].meta) == {"rows": 4}
    assert parent.tracks() == ("w7",)
    # absorbing twice in the same order is deterministic
    parent2 = Tracer()
    parent2.absorb(wire, offset=10.0, track="w7")
    assert [s.to_wire() for s in parent2.spans()] \
        == [s.to_wire() for s in parent.spans()]


def test_wire_span_roundtrip():
    tr = Tracer(track="w1")
    with tr.span("x", phase="sim", rows=2):
        pass
    [w] = tr.wire_spans()
    parent = Tracer()
    parent.absorb([w], offset=0.0, track="w1")
    [s] = parent.spans()
    assert (s.name, s.phase, dict(s.meta)) == ("x", "sim", {"rows": 2})


# ---------------- stats reset regression (satellite) ------------------------------

@pytest.mark.parametrize("cls", [CacheStats, CompileCacheStats])
def test_stats_reset_covers_every_declared_field(cls):
    """`reset()` is derived from `dataclasses.fields`, so every counter
    — including any added after this test was written — must zero."""
    stats = cls()
    for f in dataclasses.fields(stats):
        v = getattr(stats, f.name)
        if isinstance(v, dict):
            v["x"] = 7
        else:
            setattr(stats, f.name, 3)
    stats.reset()
    for f in dataclasses.fields(stats):
        v = getattr(stats, f.name)
        assert v == {} if isinstance(v, dict) else v == 0, \
            f"{cls.__name__}.{f.name} survived reset(): {v!r}"


# ---------------- timeline --------------------------------------------------------

FAULT = FaultScenario(degraded=(DiskDegradation(0, 8.0),), name="disk0x8")


@pytest.mark.parametrize("exact", [False, True])
@pytest.mark.parametrize("faults", [None, FAULT])
def test_timeline_critical_path_equals_makespan(exact, faults):
    wf = W.pipeline(4, stage_mb=(4, 8, 4, 1))
    ops = compile_workflow(wf, small_cfg(faults=faults))
    rep = jax_sim.simulate(ops, ST, exact=exact, timeline=True)
    tl = rep.timeline
    assert tl is not None and tl.n_ops == ops.n_ops
    assert tl.makespan == pytest.approx(rep.makespan)
    # interval arithmetic: start <= fin <= end, makespan = max(fin)
    assert (tl.start <= tl.fin + 1e-12).all()
    assert (tl.fin <= tl.end + 1e-12).all()
    assert tl.fin.max() == pytest.approx(tl.makespan, rel=1e-12)
    # utilization is a busy fraction of a FIFO single server
    u = tl.utilization()
    assert u.shape == (tl.n_resources,)
    assert (u >= 0.0).all() and (u <= 1.0 + 1e-9).all()
    # the chain is contiguous from t~0 and explains the whole makespan
    path = tl.critical_path()
    assert path, "empty critical path"
    assert float(tl.start[path[0]]) <= tl._tol()
    assert tl.critical_path_duration() == pytest.approx(tl.makespan,
                                                        rel=1e-9)
    # deterministic extraction
    assert path == tl.critical_path()


def test_timeline_not_built_by_default():
    wf = W.reduce_(4, in_mb=4, mid_mb=4, out_mb=8)
    ops = compile_workflow(wf, small_cfg())
    assert jax_sim.simulate(ops, ST).timeline is None


# ---------------- export ----------------------------------------------------------

def test_class_names_pin_compile_constants():
    """`export.CLASS_NAMES` is a literal copy (keeps obs core-free); this
    pins it against the real service-class constants."""
    want = {CLS_NONE: "none", CLS_NET_REMOTE: "net_remote",
            CLS_NET_LOCAL: "net_local", CLS_STORAGE: "storage",
            CLS_MANAGER: "manager", CLS_CLIENT: "client", CLS_CPU: "cpu"}
    for idx, name in want.items():
        assert CLASS_NAMES[idx] == name


def test_resource_names_follow_resource_map():
    wf = W.pipeline(2, stage_mb=(1, 1, 1, 1))
    cfg = small_cfg()
    ops = compile_workflow(wf, cfg)
    names = resource_names(cfg)
    assert len(names) == ops.n_resources
    assert names[0] == "dummy" and names[-1] == "manager"
    assert f"storage:h{cfg.storage_hosts[0]}" in names


def test_spans_to_events_structure():
    tr = Tracer()
    with tr.span("a", phase="compile", rows=2):
        pass
    tr.absorb([("b", 0.0, 0.1, "sim", ())], offset=1.0, track="w1")
    events = spans_to_events(tr.spans())
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 2 and ms
    assert {e["args"]["name"] for e in ms if e["name"] == "process_name"} \
        == {"host", "w1"}
    for e in xs:
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["dur"] >= 0.0 and e["ts"] >= 0.0
    # distinct tracks -> distinct pids
    assert len({e["pid"] for e in xs}) == 2


def test_timeline_to_events_and_write_trace(tmp_path):
    wf = W.broadcast(3, file_mb=4, replication=2)
    cfg = small_cfg()
    ops = compile_workflow(wf, cfg)
    tl = jax_sim.simulate(ops, ST, timeline=True).timeline
    tl.resource_names = tuple(resource_names(cfg))
    events = timeline_to_events(tl, label="sim")
    xs = [e for e in events if e["ph"] == "X"]
    assert xs, "no slices rendered"
    for e in xs:
        assert e["name"] in CLASS_NAMES
        assert 1 <= e["tid"] <= tl.n_resources
    # zero-duration barrier ops carry no time and are skipped
    assert len(xs) == int((tl.dur > 0).sum())
    path = write_trace(tmp_path / "t.json", events,
                       metrics={"k": np.int64(3)}, meta={"m": 1})
    doc = json.loads(path.read_text())
    assert doc["traceEvents"] and doc["otherData"]["metrics"]["k"] == 3
    assert doc["otherData"]["m"] == 1


def test_metrics_snapshot_flattens_all_counter_layers():
    with SweepSession(InlineBackend()) as sess:
        cands = grid(n_nodes=[6], chunk_sizes=[256 * 1024])
        explore(lambda c: W.pipeline(c.n_app, stage_mb=(2, 2, 2, 1)),
                cands, ST, verify_top_k=1, session=sess)
        snap = metrics_snapshot(sess, extra={"generated_at": "now"})
    assert snap["engine.batch_calls"] >= 2      # scan + verify
    assert snap["compile.grid_candidates"] == len(cands)
    assert snap["compile_count"] == compile_count()
    assert snap["generated_at"] == "now"
    # dict-valued counters flatten to <field>.<key>
    sess.stats.worker_rows["w1"] = 5
    flat = stats_snapshot(sess.stats, "engine.")
    assert flat["engine.worker_rows.w1"] == 5


# ---------------- tracing x sweep stack -------------------------------------------

def _sweep(session):
    cands = grid(n_nodes=[6, 7], chunk_sizes=[256 * 1024])
    return explore(lambda c: W.pipeline(c.n_app, stage_mb=(2, 4, 2, 1)),
                   cands, ST, verify_top_k=2, session=session)


def test_tracer_off_is_bit_identical_with_equal_counters():
    """The acceptance differential: with tracer=None the sweep performs
    the identical sequence of engine/cache operations — same makespans,
    same compile count, same batch/miss counters."""
    runs = {}
    for label, tracer in (("on", Tracer()), ("off", None)):
        n0 = compile_count()
        with SweepSession(InlineBackend(), tracer=tracer) as sess:
            evals = _sweep(sess)
            runs[label] = ([e.makespan for e in evals],
                           compile_count() - n0,
                           sess.stats.batch_calls,
                           sess.stats.exact_batch_calls,
                           sess.stats.misses,
                           sess.compile_stats.misses)
    assert runs["on"] == runs["off"]


def test_traced_sweep_records_pipeline_phases():
    tr = Tracer()
    with SweepSession(InlineBackend(), tracer=tr) as sess:
        _sweep(sess)
    phases = {s.phase for s in tr.spans()}
    assert {"compile", "host-prep", "device-sim", "exact-verify"} <= phases
    names = [s.name for s in tr.spans()]
    assert "session.prepare" in names and "compile_grid" in names
    # session default is the shared no-op
    with SweepSession(InlineBackend()) as sess:
        assert sess.tracer is NULL_TRACER
        assert sess.engine.tracer is NULL_TRACER


def test_borrowed_engine_tracer_repointed_only_on_request():
    eng = SweepEngine()
    assert eng.tracer is NULL_TRACER
    with SweepSession(InlineBackend(), engine=eng) as s1:
        assert eng.tracer is NULL_TRACER     # no tracer given: untouched
    tr = Tracer()
    with SweepSession(InlineBackend(), engine=eng, tracer=tr) as s2:
        assert eng.tracer is tr


def test_explore_timeline_top_k():
    with SweepSession(InlineBackend()) as sess:
        cands = grid(n_nodes=[6], chunk_sizes=[256 * 1024, 1 * MB])
        evals = explore(lambda c: W.pipeline(c.n_app, stage_mb=(2, 2, 2, 1)),
                        cands, ST, verify_top_k=2, timeline_top_k=1,
                        session=sess)
    best = evals[0]
    assert best.timeline is not None
    assert all(e.timeline is None for e in evals[1:])
    assert best.timeline.critical_path_duration() \
        == pytest.approx(best.timeline.makespan, rel=1e-9)
    # the re-simulation agrees with the sweep's (exact-verified) number
    assert best.timeline.makespan == pytest.approx(best.makespan, rel=1e-9)


# ---------------- multiproc span rollup -------------------------------------------

def test_multiproc_spans_merge_under_disjoint_worker_tracks():
    """Spans from >= 2 workers ship back with the counter rollup and
    merge deterministically: per-worker track ids, disjoint from the
    parent's "host" track, absorbed in item-id order."""
    tr = Tracer()
    with SweepSession(MultiprocBackend(2), tracer=tr) as sess:
        evals = _sweep(sess)
        assert sess.stats.mp_fallbacks == 0, "a worker died mid-sweep"
        rolled = set(sess.stats.worker_rows)
    tracks = tr.tracks()
    worker_tracks = {t for t in tracks if t != "host"}
    assert "host" in tracks
    assert worker_tracks == rolled, \
        f"span tracks {worker_tracks} != rolled-up workers {rolled}"
    assert all(t.startswith("w") for t in worker_tracks)
    phases = {s.phase for s in tr.spans()}
    assert {"dispatch", "merge", "compile"} <= phases
    # worker spans landed inside the parent's clock, not before dispatch
    dispatch = next(s for s in tr.spans() if s.name == "mp.dispatch")
    for s in tr.spans():
        if s.track != "host":
            assert s.start >= dispatch.start - 1e-6
    # and the sweep's values match the untraced inline reference
    with SweepSession(InlineBackend()) as ref:
        base = _sweep(ref)
    np.testing.assert_array_equal([e.makespan for e in base],
                                  [e.makespan for e in evals])


def test_multiproc_rollup_survives_worker_death_fallback(monkeypatch):
    """When every item falls back in-process (a stuck fleet whose futures
    never complete, so each item's deadline fires deterministically), the
    sweep still completes with identical results, the
    mp_items/mp_fallbacks counters record what happened, no worker
    counters are rolled up, and only host-track spans exist.

    A stuck pool rather than ``item_timeout_s`` alone: against real
    workers a warm pool (spawned by an earlier test) can finish an item
    before the parent polls, and a completed result is rightly used even
    past its deadline — which would race this test's all-items-fell-back
    assertions."""
    class StuckPool:
        def submit(self, *a, **kw):
            return concurrent.futures.Future()   # pending forever

    monkeypatch.setattr(multiproc, "_get_pool", lambda workers: StuckPool())
    cands = grid(n_nodes=[6], chunk_sizes=[256 * 1024, 1 * MB])
    wf = lambda c: W.pipeline(c.n_app, stage_mb=(2, 4, 2, 1))
    wfs = [wf(c) for c in cands]
    cfgs = [c.to_config() for c in cands]
    tr = Tracer()
    eng = SweepEngine(tracer=tr)
    mp = multiproc.MultiprocSweep(wfs, cfgs, st=ST, workers=2, engine=eng,
                                  cache=CompileCache(), item_timeout_s=1e-9,
                                  tracer=tr)
    got = mp.simulate()
    assert eng.stats.mp_fallbacks > 0
    assert eng.stats.mp_items >= eng.stats.mp_fallbacks
    assert eng.stats.worker_rows == {}          # nothing rolled up
    assert tr.tracks() == ("host",)             # no worker spans arrived
    phases = {s.phase for s in tr.spans()}
    assert {"dispatch", "merge"} <= phases
    # fallback execution is traced too (parent engine wears the tracer)
    assert "device-sim" in phases
    ops = [compile_workflow(w, c) for w, c in zip(wfs, cfgs)]
    want = SweepEngine().simulate_batch(ops, [ST] * len(ops))
    np.testing.assert_array_equal(want, got)


def test_multiproc_broken_pool_rollup_with_tracer(monkeypatch):
    """A dead pool degrades every item in-process: results unchanged,
    rollups intact, tracer keeps recording."""
    class BrokenPool:
        def submit(self, *a, **kw):
            raise RuntimeError("cannot schedule new futures after shutdown")

    monkeypatch.setattr(multiproc, "_get_pool", lambda workers: BrokenPool())
    tr = Tracer()
    eng = SweepEngine(tracer=tr)
    cands = grid(n_nodes=[6], chunk_sizes=[256 * 1024])
    evals = explore(lambda c: W.pipeline(c.n_app, stage_mb=(2, 2, 2, 1)),
                    cands, ST, verify_top_k=1, engine=eng,
                    compile_cache=CompileCache(), workers=2)
    assert eng.stats.mp_fallbacks > 0
    assert eng.stats.worker_rows == {}
    assert tr.tracks() == ("host",)
    with SweepSession(InlineBackend()) as ref:
        base = explore(lambda c: W.pipeline(c.n_app, stage_mb=(2, 2, 2, 1)),
                       cands, ST, verify_top_k=1, session=ref)
    np.testing.assert_array_equal([e.makespan for e in base],
                                  [e.makespan for e in evals])


# ---------------- search, host-prep split, executable names ------------------------

def _within(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end + 1e-9


@pytest.mark.parametrize("entry", ["explore", "explore_many",
                                   "successive_halving"])
def test_search_entry_span_wraps_its_sweep(entry):
    from repro.core.sweep import search
    tr = Tracer()
    cands = grid(n_nodes=[6], chunk_sizes=[256 * 1024])

    def wf(c):
        return W.pipeline(c.n_app, stage_mb=(2, 4, 2, 1))
    with SweepSession(InlineBackend(), tracer=tr) as sess:
        if entry == "explore_many":
            search.explore_many([wf], cands, ST, verify_top_k=2,
                                session=sess)
        else:
            getattr(search, entry)(wf, cands, ST, session=sess)
    [top] = [s for s in tr.spans() if s.phase == "search"]
    assert top.name == entry and dict(top.meta)["candidates"] == len(cands)
    assert all(_within(s, top) for s in tr.spans() if s is not top)


def test_host_prep_split_nests_in_prep_and_warm_repeat_records_none():
    tr = Tracer()
    with SweepSession(InlineBackend(), tracer=tr) as sess:
        _sweep(sess)
        spans = tr.spans()
        preps = [s for s in spans if s.phase == "host-prep"]
        parts = [s for s in spans
                 if s.phase in ("host-order", "host-pack", "host-stack")]
        assert {s.phase for s in parts} == {"host-order", "host-pack",
                                            "host-stack"}
        assert all(any(_within(s, p) for p in preps) for s in parts)
        # one pack per row miss, one order per scan-mode row miss
        st = sess.stats
        assert sum(s.name == "pack" for s in parts) == st.row_misses
        assert sum(s.name == "stack" for s in parts) == st.stack_misses
        assert 0 < sum(s.name == "order" for s in parts) < st.row_misses
        # a warm repeat hits the row and stack caches: no split spans
        misses = (st.row_misses, st.stack_misses)
        tr.clear()
        _sweep(sess)
        assert (st.row_misses, st.stack_misses) == misses
        assert any(s.phase == "host-prep" for s in tr.spans())
        assert not [s for s in tr.spans()
                    if s.phase in ("host-order", "host-pack", "host-stack")]


def _module_name(exact: bool, faulted: bool, kernel: bool = False,
                 mesh=None) -> str:
    """The module an engine executable lowers to, at a tiny bucket (four
    rows, so a four-way mesh splits them)."""
    import re
    import jax
    import jax.numpy as jnp
    from repro.core.compile import MAXD
    from repro.core.sweep.engine import _make_executable
    from repro.core.x64 import enable_x64
    c, n, r = 4, 16, 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    with enable_x64():
        f = jnp.float64
        args = [jax_sim.OpArrays(
                    res=s((c, n), jnp.int32), cls=s((c, n), jnp.int32),
                    nbytes=s((c, n), f), reqs=s((c, n), f),
                    extra=s((c, n), f), nlat=s((c, n), f),
                    deps=s((c, n, MAXD), jnp.int32)),
                s((c, 7), f)]
        if faulted:
            args.append(jax_sim.FaultArrays(res_mult=s((c, r), f),
                                            dead=s((c, n), f)))
        fn = _make_executable(r, exact, mesh=mesh, faulted=faulted,
                              kernel=kernel)
        text = fn.lower(*args).as_text()
    return re.match(r"module @(\S+) ", text).group(1)


@pytest.mark.parametrize("exact,faulted,kernel,name", [
    (False, False, False, "jit_sim_scan"),
    (False, True, False, "jit_sim_scan"),
    (True, False, False, "jit_sim_exact"),
    (True, True, False, "jit_sim_exact"),
    (False, False, True, "jit_scan_batch"),
])
def test_engine_executables_have_stable_names(exact, faulted, kernel, name):
    """A device trace names each executable by its mode, not by the
    bucket it was built for."""
    assert _module_name(exact, faulted, kernel) == name


def test_sharded_executables_keep_their_stable_names():
    """On a mesh the executable is jit(shard_map(vmap(...))); it keeps
    its mode's name. Four host devices are forced in a fresh process,
    since a process's device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    prog = (
        "import jax, numpy as np\n"
        "from jax.sharding import Mesh\n"
        "from repro.core.sweep import SHARD_AXIS\n"
        "from test_obs import _module_name\n"
        "mesh = Mesh(np.asarray(jax.devices()[:4]), (SHARD_AXIS,))\n"
        "assert mesh.devices.size == 4, jax.devices()\n"
        "for exact in (False, True):\n"
        "    for faulted in (False, True):\n"
        "        print(_module_name(exact, faulted, mesh=mesh))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(root / "src"),
                                          str(root / "tests")]),
           "XLA_FLAGS": " ".join(filter(None, (
               os.environ.get("XLA_FLAGS"),
               "--xla_force_host_platform_device_count=4")))}
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["jit_sim_scan", "jit_sim_scan",
                                  "jit_sim_exact", "jit_sim_exact"]


# ---------------- spans on the profiler's clock ------------------------------------

def test_annotated_tracer_writes_profiler_events(tmp_path):
    """A `Tracer` under a profiler session writes each span as a
    ``<name>|<phase>`` host event with the meta as stats (a tuple as one
    space-separated string), and two spans closed out of nesting order
    on one thread (as an event loop closes a queue span) both
    survive."""
    import jax
    from jax.profiler import ProfileData
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        a = tr.span("request", phase="serve", req=7)
        a.__enter__()
        b = tr.span("queued", phase="serve-queue", req=7, reqs=(7, 8))
        b.__enter__()
        time.sleep(0.002)
        a.__exit__(None, None, None)
        b.__exit__(None, None, None)
    [path] = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = {e.name: dict(e.stats)
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events}
    assert events["request|serve"] == {"req": 7}
    assert events["queued|serve-queue"] == {"req": 7, "reqs": "7 8"}
    # the host-clock record is kept as before
    assert [s.name for s in tr.spans()] == ["request", "queued"]
    assert dict(tr.spans()[1].meta)["reqs"] == (7, 8)
