"""`correct` comes out false when the timed path is broken underneath,
and for the lower-precision control; true on the sound program. Runs a
cell's rehearsal sizes on the CPU, in this process."""
import contextlib
import io
import json
from pathlib import Path

import numpy as np
import pytest

CELLS = ["blast-sc1.whatif", "synth-micro.advisor", "synth-micro.scan"]


def run_cell(name, seed=5, seconds=1.0):
    from bench import run
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", "0",
                         "--rehearse"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def engine():
    from repro.core.sweep.engine import SweepEngine
    return SweepEngine


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    res = run_cell(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_f32_control_is_not_correct(name, monkeypatch):
    monkeypatch.setenv("REPRO_SIM_X64", "0")
    res = run_cell(name, seed=6)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_is_not_correct(name, engine, monkeypatch):
    real = engine.simulate_batch

    def altered(self, ops_list, st_list, *, exact=False):
        out = real(self, ops_list, st_list, exact=exact)
        out[len(out) // 2] *= 1 + 1e-6
        return out

    monkeypatch.setattr(engine, "simulate_batch", altered)
    res = run_cell(name, seed=7)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_half_batch_left_out_is_not_correct(name, engine, monkeypatch):
    real = engine.simulate_batch

    def half(self, ops_list, st_list, *, exact=False):
        keep = max(len(ops_list) // 2, 1)
        out = real(self, ops_list[:keep], st_list[:keep], exact=exact)
        # the rows left out get the mean of the rows that ran
        return np.concatenate([out, np.full(len(ops_list) - keep,
                                            out.mean())])

    monkeypatch.setattr(engine, "simulate_batch", half)
    res = run_cell(name, seed=8)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("mix,config", [("zipf", "synth-micro"),
                                        ("cold", "blast-sc1")])
def test_a_mix_added_as_data_runs(mix, config, monkeypatch):
    """A cell whose mix is a new data file runs with no code added:
    recurring bursty questions, and a new DAG per sweep."""
    from bench import spec as S
    bench = S.load_benchmark()
    name = f"{config}.{mix}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": mix, "chips": 1, "why": "test"})
    data = S.BENCH / "tests" / "data"
    real = S.traffic_path
    monkeypatch.setattr(S, "load_benchmark", lambda root=S.ROOT: bench)
    monkeypatch.setattr(S, "traffic_path", lambda n: data / f"{n}.json"
                        if n == mix else real(n))
    res = run_cell(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 2 and res["failed"] == 0
    assert "setup_s" in res["metrics"]


def test_a_near_tie_may_land_either_side():
    """A reduce whose makespan jumps by 1.4e-4 when its service times
    move by 1e-14: a served makespan from the other side of the tie is
    within the limit, one altered by 1e-6 is not."""
    from bench import generator as G
    from bench.check import Gaps, RefDags, rel_gap
    from bench.reference.sim import exact_makespan
    cfg = json.loads((Path(__file__).parents[1] / "configs"
                      / "synth-micro.json").read_text())
    st = G.service_times(cfg["service_times"]["ramdisk"])
    wf = G.build_workflow(cfg, {"family": "reduce", "wass": False}, 9, G.KB)
    dag = RefDags().dag(wf, G.layouts_for(cfg, (9, 10))[0])
    rng = np.random.default_rng(0)
    nominal = exact_makespan(dag, st)
    other = next(m for m in (
        exact_makespan(dag, {k: v * (1 + 1e-14 * rng.standard_normal())
                             for k, v in st.items()}) for _ in range(64))
        if rel_gap(m, nominal) > 1e-5)
    for served, ok in ((nominal, True), (other, True),
                       (other * (1 + 1e-6), False)):
        gaps = Gaps(exact_makespan, 1e-8, np.random.default_rng(1))
        gaps.add(served, dag, st)
        assert (gaps.widest <= 1e-8) == ok, gaps.widest
