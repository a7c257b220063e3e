"""`BENCHMARK.json` resolves by name to its files, keeps to the
benchmark's naming rules, and every per-layer metric's cells report the
end-to-end metric it moves."""
import json
import re

import pytest

from bench import spec as S

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = S.load_benchmark()
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == KEYS
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = S.Cell(BENCH, name)
    assert cell.config["name"] == cell.entry["config"]
    assert cell.config_entry["file"].startswith("bench/configs/")
    assert S.traffic_path(cell.entry["traffic"]).is_file()
    assert callable(S.load_driver(cell.traffic["driver"]))
    for m in cell.end_to_end:
        assert callable(S.load_e2e(m["name"]))
    for m in cell.per_layer:
        assert callable(S.load_reader(m["name"]))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    assert cell.chips in (1, 4)


def test_names_units_and_lines():
    named = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for entry in named:
        assert NAME_RE.match(entry["name"]), entry["name"]
    for w in BENCH["workloads"]:
        assert NAME_RE.match(w["config"]) and NAME_RE.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert all(NAME_RE.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200
        assert json.loads(open(S.ROOT / c["file"]).read())["name"] == c["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200
    names = [e["name"] for e in named]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_cells_report_what_it_moves(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    moved = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert cell in moved.get("workloads", CELLS)
    assert re.fullmatch(r"[A-Za-z0-9_./-]+", metric)
