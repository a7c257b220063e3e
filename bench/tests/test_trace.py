"""The trace reduction on a recorded trace: an 8 s traced window of
synth-micro.advisor on one TPU v5e (`data/advisor.xplane.pb`), and the
roofline byte count on rows whose size is known."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import workflows as W
from bench.check import RefDags
from bench.layers import _common as C
from bench.tracedata import clip, covered, reduce_trace, union

TRACE = Path(__file__).parent / "data" / "advisor.xplane.pb"


@pytest.fixture(scope="module")
def td():
    return reduce_trace(TRACE)


def test_interval_arithmetic():
    assert union([[3, 4], [0, 2], [1, 2.5]]) == [[0, 2.5], [3, 4]]
    assert clip([[0, 2], [3, 5]], 1, 4) == [[1, 2], [3, 4]]
    assert covered([[0, 2], [1, 3], [5, 6]]) == 4


def test_busy_and_idle(td):
    assert td.window_s == pytest.approx(7.625598778, abs=1e-9)
    assert td.busy_s() == pytest.approx(3.295657309, abs=1e-9)
    ctx = {"trace": td}
    assert C.device_idle(ctx) == pytest.approx(
        100 * (1 - 3.295657309 / 7.625598778), abs=1e-7)


def test_time_per_executable_kind(td):
    secs = {k: sum(m.end - m.start for m in td.modules(k))
            for k in ("scan", "exact", "other")}
    assert secs["scan"] == pytest.approx(0.038892018, abs=1e-9)
    assert secs["exact"] == pytest.approx(3.255814575, abs=1e-9)
    assert secs["other"] == pytest.approx(0.000950716, abs=1e-9)
    assert len(td.modules("scan")) == 10 and len(td.modules("exact")) == 10
    assert td.sim_steps("device-sim") == 8192
    assert td.sim_steps("exact-verify") == 8192
    kinds = dict(td.device_ops())
    assert kinds["exact:jit_one"] == pytest.approx(secs["exact"])


def test_program_span_shares(td):
    assert td.phase_share("exact-verify") == pytest.approx(0.450184224, abs=1e-8)
    assert td.phase_share("compile") == pytest.approx(0.006455869, abs=1e-8)
    assert td.phase_share("host-prep") == pytest.approx(0.049593876, abs=1e-8)
    gaps = dict(td.idle_gaps())
    assert sum(gaps.values()) == pytest.approx(td.window_s - td.busy_s(),
                                               abs=1e-6)
    assert len(td.idle_gaps()) <= 10


def test_roofline_byte_count(td):
    lay = W.layout(2, 2, chunk_size=W.MB)
    wf = W.broadcast(2, file_bytes=2 * W.MB, out_bytes=W.MB, replication=1)
    refs = RefDags()
    n = refs.count(wf, lay)
    req = SimpleNamespace(workflows=[wf, wf], layouts=[lay, lay])
    o = SimpleNamespace(error="", req=req,
                        ranked=[(0, 1.0, 1.0, True), (1, 2.0, 2.0, False)])
    ctx = {"outcomes": [o], "refs": refs, "trace": td,
           "device_kind": "TPU v5 lite",
           "peaks": {"devices": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}}}
    assert C.OP_BYTES == 56 and C.ROW_BYTES == 64
    assert C.least_bytes(ctx, "scan") == 2 * (56 * n + 64)
    assert C.least_bytes(ctx, "exact") == 56 * n + 64
    share = C.roofline(ctx, "exact")
    assert share == pytest.approx(100 * (56 * n + 64) / 819e9 / 3.255814575)
    with pytest.raises(KeyError):
        C.roofline(dict(ctx, device_kind="TPU v9"), "exact")
