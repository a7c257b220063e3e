"""Workflow families found by name, the same requests for every cell and
seed as before families were files, the cell's chips picking the
program's backend, and the per-chip readers on a hand-built trace."""
import hashlib
import json
import math

import pytest

from bench import generator as G
from bench import spec as S
from bench.tracedata import Module, Span, TraceData

# sha256 (first 16 hex digits) of every request a cell's generator
# makes for one seed at the configuration's full sizes: the warm-up,
# then an open loop's whole window or a closed loop's first 12, with
# their due times. Recorded before the families moved into files;
# sharded4 is the whatif mix on more chips, so it reads whatif's.
SEEDS = (7, 2**31 + 11)
DIGESTS = {
    "blast-sc1.whatif": ("aff84387ce026d50", "706dfab7b0a89ae8"),
    "blast-sc1.sharded4": ("aff84387ce026d50", "706dfab7b0a89ae8"),
    "synth-micro.advisor": ("f071029789020e30", "f11a98900a1e1fa3"),
    "synth-micro.scan": ("4f1ee346a19cd9d2", "cb0efea5fd7afc91"),
}


def request_digest(name, seed, n_closed=12):
    bench = S.load_benchmark()
    cell = S.Cell(bench, name)
    gen = G.Generator(cell.config, cell.traffic, seed, bench["run_seconds"])
    h = hashlib.sha256()

    def add(due, r):
        h.update(json.dumps([due, r.workflows, r.layouts, r.st,
                             r.verify_top_k], sort_keys=True).encode())

    for r in gen.warmup():
        add(None, r)
    for _ in range(n_closed if gen.dues is None else len(gen.dues) + 1):
        due, r = gen.next()
        add(due if due is None or math.isfinite(due) else "inf", r)
    return h.hexdigest()[:16]


@pytest.mark.parametrize("name,k", [(n, k) for n in DIGESTS
                                    for k in range(len(SEEDS))])
def test_requests_are_byte_identical(name, k):
    assert request_digest(name, SEEDS[k]) == DIGESTS[name][k]


TOY = '''
from bench import workflows as W


def build(cfg, spec, n_app, cut):
    """A pipeline: each app node's task reads the one before's file."""
    size = cfg["pipe_mb"] * W.MB - cut
    tasks = [W._task(k, [f"f{k}"], [[f"f{k + 1}", size]], client=k,
                     runtime=spec["runtime_s"], stage="pipe")
             for k in range(n_app)]
    return {"name": f"pipe_{n_app}", "tasks": tasks,
            "preloaded": [["f0", size, None]]}
'''


@pytest.fixture
def families(tmp_path, monkeypatch):
    """A benchmark tree whose families directory holds only a toy."""
    fam = tmp_path / "bench" / "families"
    fam.mkdir(parents=True)
    (fam / "toy.py").write_text(TOY)
    monkeypatch.setattr(S, "ROOT", tmp_path)
    monkeypatch.setattr(S, "BENCH", tmp_path / "bench")
    S.load_family.cache_clear()
    yield fam
    S.load_family.cache_clear()


def test_a_family_file_builds_requests_with_no_edit(families):
    from bench.check import RefDags
    cfg = {"n_nodes": 6, "partitions": [[2, 3], [3, 2]], "chunk_kb": [1024],
           "stripe_widths": [0], "replications": [1], "pipe_mb": 3,
           "workflows": [{"family": "toy", "runtime_s": 1.5}],
           "service_profile": "p",
           "service_times": {"p": {"net_remote_Bps": 1e8,
                                   "net_local_Bps": 2e9,
                                   "net_latency_s": 1e-4,
                                   "storage_Bps": 1e9, "manager_s": 4e-4,
                                   "client_s": 0.0, "storage_req_s": 3e-4}}}
    mix = {"driver": "sweep", "arrivals": {"process": "closed"},
           "question": "grid", "verify_top_k": 0, "size_jitter_kb": 7}
    _, req = G.Generator(cfg, mix, 2**31 + 3, 10.0).next()
    assert [wf["name"] for wf in req.workflows] == ["pipe_2", "pipe_3"]
    assert len(req.workflows[1]["tasks"]) == 3
    assert req.workflows[0]["preloaded"][0][1] < 3 * G.W.MB
    refs = RefDags()
    assert all(refs.count(wf, lay) > 0
               for wf, lay in zip(req.workflows, req.layouts))


def test_an_unknown_family_names_the_file_it_looked_for(families):
    with pytest.raises(FileNotFoundError, match="bench/families/nosuch.py"):
        G.build_workflow({}, {"family": "nosuch"}, 2)


@pytest.mark.parametrize("name,backend", [
    ("blast-sc1.sharded4", "ShardedBackend"),
    ("blast-sc1.whatif", "InlineBackend"),
    ("synth-micro.advisor", "InlineBackend")])
def test_the_cells_chips_pick_the_backend(name, backend, tmp_path):
    from bench.drivers._base import Base
    cell = S.Cell(S.load_benchmark(), name)
    drv = Base(cell, None, tracer=None, dag_dir=tmp_path, hooks=None)
    sess = drv.session()
    try:
        assert type(sess.backend).__name__ == backend
        if cell.chips > 1:
            assert sess.backend.devices == cell.chips == 4
            assert sess.backend.min_shard_oprows is None
    finally:
        sess.close()


def chips_trace(busy_to, chips=4, steps=(8192, 16384)):
    """A 10 s window. Chip k runs one scan executable from 0 to
    ``busy_to[k]`` s and one untagged executable from 9.5 to 10 s; the
    host runs one ``sim`` span per step count and 2 s of host prep."""
    mods = {f"/device:TPU:{k}": [Module("jit_sim_scan", 0.0, b, "scan"),
                                 Module("jit_copy", 9.5, 10.0)]
            for k, b in enumerate(busy_to)}
    spans = [Span("window", "bench", 0.0, 10.0),
             Span("prep[16384x128]", "host-prep", 8.0, 9.0),
             Span("prep[16384x128]", "host-prep", 8.5, 9.5)]
    spans += [Span(f"sim[{n}x128x16]", "device-sim", 0.1 * i, 0.1 * i + 0.1)
              for i, n in enumerate(steps)]
    return {"trace": TraceData(window=(0.0, 10.0), devices=mods,
                               spans=spans), "chips": chips}


def read(name, ctx):
    return S.load_reader(name)(ctx)


def test_per_chip_readers():
    ctx = chips_trace([6.0, 5.0, 5.0, 4.0])
    # busy 6.5, 5.5, 5.5, 4.5 s: mean 5.5, the busiest 6.5
    assert read("device_idle.sharded4", ctx) == pytest.approx(45.0)
    assert read("chip_balance.sharded4", ctx) == pytest.approx(
        100 * 5.5 / 6.5)
    # device 0 alone: 6 s of scan over 24,576 steps
    assert read("scan_step_us.sharded4", ctx) == pytest.approx(
        6e6 / 24576)
    assert read("host_prep_share.sharded4", ctx) == pytest.approx(15.0)


def test_a_chip_without_executables_reads_idle():
    """Device 0 did all the work and the others left no line."""
    ctx = chips_trace([6.0])
    assert read("chip_balance.sharded4", ctx) == pytest.approx(25.0)
    assert read("device_idle.sharded4", ctx) == pytest.approx(
        100 * (1 - 6.5 / 40))


def test_device_zero_is_the_lowest_numbered_plane():
    ctx = chips_trace([6.0, 2.0, 2.0, 2.0])
    td = ctx["trace"]
    td.devices = dict(reversed(list(td.devices.items())))
    assert read("scan_step_us.sharded4", ctx) == pytest.approx(
        6e6 / 24576)


@pytest.mark.parametrize("name", ["device_idle.sharded4",
                                  "chip_balance.sharded4",
                                  "scan_step_us.sharded4",
                                  "host_prep_share.sharded4"])
def test_per_chip_readers_are_silent_on_an_empty_trace(name):
    ctx = {"trace": TraceData(window=(0.0, 10.0), devices={},
                              spans=[Span("window", "bench", 0.0, 10.0)]),
           "chips": 4}
    assert read(name, ctx) is None
