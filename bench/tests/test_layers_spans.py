"""The readers of the program's serve and host-prep sub-spans, on a
hand-built trace whose every number is known, and on a trace of a
program without those spans."""
import pytest

from bench import spec as S
from bench.tracedata import Module, Span, TraceData

NEW = ["queue_wait_mean_s.answer", "queue_wait_p50_s.screen",
       "idle_in_service.answer", "idle_in_service.screen",
       "scan_order_share.sweep", "pack_share.sweep", "stack_share.sweep",
       "scan_order_share.screen"]


def trace(spans):
    """A 10 s window; the first device runs 1-3 s and 6-7 s, a second
    device (which the serve readers do not read) all the window."""
    mods = {"/device:TPU:0": [Module("jit_sim_scan", 1.0, 3.0),
                              Module("jit_sim_exact", 6.0, 7.0)],
            "/device:TPU:1": [Module("jit_sim_scan", 0.0, 10.0)]}
    return {"trace": TraceData(window=(0.0, 10.0), devices=mods,
                               spans=[Span("window", "bench", 0.0, 10.0)]
                               + spans)}


SERVE = [
    # straddles the first busy interval: idle 0.5-1 and 3-4
    Span("request", "serve", 0.5, 4.0),
    Span("queued", "serve-queue", 0.5, 0.502),
    # overlaps the first request: idle 4-4.5
    Span("request", "serve", 2.0, 4.5),
    Span("queued", "serve-queue", 2.0, 3.1),
    # starts before the window: its wait is not counted, its idle is
    Span("request", "serve", -1.0, 0.25),
    Span("queued", "serve-queue", -1.0, -0.5),
    # runs past the window's end: idle 9-10 only
    Span("request", "serve", 9.0, 12.0),
    Span("queued", "serve-queue", 9.0, 9.01),
    Span("dispatch", "serve", 3.1, 4.5),
]


def read(name, ctx):
    return S.load_reader(name)(ctx)


def test_queue_wait_mean_of_waits_that_start_in_the_window():
    # waits 0.002, 1.1, 0.01 (the wait that starts before the window is
    # left out): their mean is 0.370666...
    assert read("queue_wait_mean_s.answer", trace(SERVE)) == \
        pytest.approx(1.112 / 3)


def test_queue_wait_median_of_waits_that_start_in_the_window():
    # waits 0.002, 1.1, 0.01: the nearest-rank median is 0.01
    assert read("queue_wait_p50_s.screen", trace(SERVE)) == \
        pytest.approx(0.01)


@pytest.mark.parametrize("cell", ["answer", "screen"])
def test_idle_in_service_counts_idle_time_under_an_open_request(cell):
    # 0-0.25, 0.5-1, 3-4.5, 9-10: 3.25 s of a 10 s window
    assert read(f"idle_in_service.{cell}", trace(SERVE)) == \
        pytest.approx(32.5)


def test_host_prep_sub_phase_shares():
    spans = [Span("prep[1024x128]", "host-prep", 0.0, 6.0),
             Span("order", "host-order", 0.0, 2.0),
             Span("order", "host-order", 2.5, 3.5),
             Span("pack", "host-pack", 3.5, 5.0),
             Span("stack", "host-stack", 5.0, 5.25)]
    ctx = trace(spans)
    assert read("scan_order_share.sweep", ctx) == pytest.approx(30.0)
    assert read("scan_order_share.screen", ctx) == pytest.approx(30.0)
    assert read("pack_share.sweep", ctx) == pytest.approx(15.0)
    assert read("stack_share.sweep", ctx) == pytest.approx(2.5)


@pytest.mark.parametrize("name", NEW)
def test_silent_on_a_program_without_the_spans(name):
    """The parent program records neither serve nor sub-phase spans."""
    spans = [Span("prep[1024x128]", "host-prep", 0.0, 6.0),
             Span("sim[1024x128x4]", "device-sim", 1.0, 3.0)]
    assert read(name, trace(spans)) is None


def test_every_new_metric_has_a_reader_and_one_cell():
    entries = {m["name"]: m for m in S.load_benchmark()["per_layer"]}
    for name in NEW:
        assert S.layer_path(name).is_file()
        assert len(entries[name]["workloads"]) == 1
