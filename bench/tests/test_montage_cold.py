"""`montage-4deg.whatif` and `blast-sc1.cold` rehearsed on the CPU, each
run a process of its own (`REPRO_SIM_X64` is read when JAX starts):
`correct` is true on the sound program, and false on the Montage cell
for the f32 control and for an answer altered underneath. Then the
Montage cell's readers, on a hand-built trace and on one of a program
without the host-order-loop span."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec as S
from bench.tracedata import Module, Span, TraceData

CHILD = Path(__file__).with_name("_rehearse.py")
MONTAGE = "montage-4deg.whatif"


def rehearse(cell, fault, seed, *, x64=True, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               REPRO_SIM_X64="1" if x64 else "0")
    args = [sys.executable, str(CHILD), fault, "--workload", cell,
            "--seed", str(seed), "--seconds", "1", "--trace", str(trace),
            "--rehearse"]
    done = subprocess.run(args, env=env, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [MONTAGE, "blast-sc1.cold"])
def test_sound_program_is_correct(cell):
    res = rehearse(cell, "none", 2**31 + 31)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"sweep_rows_per_s", "setup_s"}


def test_f32_control_is_not_correct_on_montage():
    res = rehearse(MONTAGE, "none", 2**31 + 32, x64=False)
    assert not res["correct"], res["checks"]


def test_altered_answer_is_not_correct_on_montage():
    res = rehearse(MONTAGE, "altered", 2**31 + 33)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell,metrics", [
    (MONTAGE, {"order_loop_share.montage", "scan_order_share.montage",
               "host_prep_share.montage"}),
    ("blast-sc1.cold", {"dag_compile_share.cold"})])
def test_traced_rehearsal_reads_the_span_metrics(cell, metrics):
    """On the CPU the trace has no device plane: the device readers
    give nothing, the span readers give their shares."""
    res = rehearse(cell, "none", 2**31 + 34, trace=1)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == metrics
    assert all(0 < m["value"] <= 100 for m in res["metrics"].values())


def trace(spans):
    """A 10 s window; the chip runs the scan 1-5 s and 6-9 s."""
    mods = {"/device:TPU:0": [Module("jit_sim_scan", 1.0, 5.0, "scan"),
                              Module("jit_sim_scan", 6.0, 9.0, "scan")]}
    return {"trace": TraceData(window=(0.0, 10.0), devices=mods,
                               spans=[Span("window", "bench", 0.0, 10.0)]
                               + spans)}


PREP = [Span("prep[524288x32]", "host-prep", 0.0, 1.0),
        Span("order", "host-order", 0.1, 0.9),
        Span("prep[262144x32]", "host-prep", 5.0, 6.0),
        Span("order", "host-order", 5.1, 5.5),
        Span("sim[524288x32x4]", "device-sim", 1.0, 5.0),
        Span("sim[262144x32x4]", "device-sim", 6.0, 9.0)]
LOOP = [Span("order_loop", "host-order-loop", 0.2, 0.8)]


def read(name, ctx):
    return S.load_reader(name)(ctx)


def test_montage_readers_on_a_known_trace():
    ctx = trace(PREP + LOOP)
    assert read("order_loop_share.montage", ctx) == pytest.approx(6.0)
    assert read("scan_order_share.montage", ctx) == pytest.approx(12.0)
    assert read("host_prep_share.montage", ctx) == pytest.approx(20.0)
    assert read("device_idle.montage", ctx) == pytest.approx(30.0)
    # 7 s of scan over 2^19 + 2^18 sequential steps
    assert read("scan_step_us.montage", ctx) == \
        pytest.approx(7e6 / (524288 + 262144))


def test_loop_share_is_silent_without_the_span():
    """A program without the host-order-loop span (the parent's): the
    new reader gives nothing, the others read as before."""
    ctx = trace(PREP)
    assert read("order_loop_share.montage", ctx) is None
    assert read("scan_order_share.montage", ctx) == pytest.approx(12.0)
