"""Benchmark-artifact correctness: the versioned ``dryrun_results.json``
format (`repro.launch.dryrun_meta`) and the SKIP/ERROR status tagging in
the benchmark rows.

Two bugfix pins live here:

  * a stale persisted dry-run (legacy bare list, format bump, or digest
    mismatch after a roofline-constant change) must read as *absent* —
    the roofline benchmark recomputes instead of reporting fractions
    against outdated roofs;
  * the ``-1.0`` / ``-2.0`` SKIP/ERROR sentinel values are not scores:
    rows carry ``status`` into the ``--json`` artifact and sentinel
    rows are excluded from the worst-cell aggregate (a -1.0 "score"
    once ranked as the best roofline fraction in a trend query).
"""
import json

import pytest

from repro.launch.dryrun_meta import (FORMAT_VERSION, dryrun_digest,
                                      unwrap_results, wrap_results)

from benchmarks import roofline
from benchmarks import run as bench_run
from benchmarks.common import Row


# ---------------- dryrun_meta format/digest ---------------------------------------

CELLS = [{"arch": "a", "shape": "s", "roofline_fraction": 0.5,
          "dominant": "compute", "t_compute_s": 1.0, "t_memory_s": 0.5,
          "t_collective_s": 0.1, "useful_flops_ratio": 0.9,
          "fits_hbm": True, "bytes_per_device": 2 ** 30}]


def test_wrap_unwrap_round_trip():
    cells, stale = unwrap_results(wrap_results(CELLS))
    assert not stale and cells == CELLS


def test_wrap_survives_json_round_trip(tmp_path):
    p = tmp_path / "dryrun_results.json"
    p.write_text(json.dumps(wrap_results(CELLS)))
    cells, stale = unwrap_results(json.loads(p.read_text()))
    assert not stale and cells == CELLS


@pytest.mark.parametrize("payload,why", [
    (CELLS, "legacy"),                                     # bare list
    ({"meta": {"format_version": FORMAT_VERSION - 1,
               "digest": dryrun_digest()}, "cells": CELLS}, "format_version"),
    ({"meta": {"format_version": FORMAT_VERSION,
               "digest": "feedfacedeadbeef"}, "cells": CELLS}, "digest"),
    ({"meta": {"format_version": FORMAT_VERSION,
               "digest": dryrun_digest()}}, "cells"),
    ("what", "unrecognized"),
])
def test_stale_artifacts_rejected(payload, why):
    cells, stale = unwrap_results(payload)
    assert cells is None and why in stale


def test_digest_tracks_constants(monkeypatch):
    before = dryrun_digest()
    import repro.launch.dryrun_meta as meta
    monkeypatch.setattr(meta, "PEAK_FLOPS", 1.0)
    assert dryrun_digest() != before


# ---------------- roofline reader -------------------------------------------------

GOOD = dict(CELLS[0])
WORSE = {**GOOD, "shape": "s2", "roofline_fraction": 0.3}
SKIP = {"arch": "a", "shape": "s3", "skipped": "O(L^2) at 500k"}
ERROR = {"arch": "a", "shape": "s4", "error": "boom"}


def test_row_statuses():
    assert roofline._row(GOOD).status == "ok"
    skip = roofline._row(SKIP)
    assert (skip.status, skip.value) == ("skip", -1.0)
    err = roofline._row(ERROR)
    assert (err.status, err.value) == ("error", -2.0)


def test_worst_cell_excludes_sentinels(tmp_path, monkeypatch):
    p = tmp_path / "dryrun_results.json"
    p.write_text(json.dumps(wrap_results([GOOD, WORSE, SKIP, ERROR])))
    monkeypatch.setattr(roofline, "RESULTS", str(p))
    rows = {r.name: r for r in roofline.roofline_table()}
    worst = rows["roofline/worst_cell"]
    assert worst.value == pytest.approx(0.3), \
        "a SKIP/ERROR sentinel leaked into the worst-cell aggregate"
    assert rows["roofline/a/s3"].status == "skip"
    assert rows["roofline/a/s4"].status == "error"


def test_stale_results_fall_back_to_live_subset(tmp_path, monkeypatch):
    p = tmp_path / "dryrun_results.json"
    p.write_text(json.dumps(CELLS))                       # legacy bare list
    monkeypatch.setattr(roofline, "RESULTS", str(p))
    calls = []
    monkeypatch.setattr(roofline, "_live_subset",
                        lambda note: calls.append(note) or [])
    assert roofline.roofline_table() == []
    assert calls and "stale" in calls[0] and "legacy" in calls[0]


# ---------------- run.py JSON artifact --------------------------------------------

def test_status_flows_into_json_artifact(tmp_path, monkeypatch, capsys):
    rows = [Row("toy/metric", 1.5, "fine"),
            Row("toy/skipped", -1.0, "SKIP: nope", status="skip"),
            Row("toy/errored", -2.0, "ERROR: boom", status="error")]
    monkeypatch.setattr(bench_run, "all_benchmarks",
                        lambda: {"toy": lambda: rows})
    # with the variable set, main's compile-cache helper leaves this
    # process's JAX config alone (no cache leaks into later tests)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    out = tmp_path / "bench.json"
    assert bench_run.main(["--only", "toy", "--json", str(out)]) == 0
    capsys.readouterr()
    recs = {r["name"]: r for r in json.loads(out.read_text())["benchmarks"]}
    assert recs["toy/metric"]["status"] == "ok"
    assert recs["toy/skipped"]["status"] == "skip"
    assert recs["toy/errored"]["status"] == "error"
    assert recs["toy/_wall_s"]["status"] == "ok"
