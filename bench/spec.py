"""`BENCHMARK.json` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:

    bench/configs/<config>.json   the deployment (`file` in the entry)
    bench/traffic/<traffic>.json  the mix's parameters, for `generator`
    bench/drivers/<driver>.py     the entry a mix is served through
                                  (its ``driver``), a class `Driver`
    bench/families/<family>.py    a workflow family a configuration's
                                  ``workflows`` name (``family``), a
                                  function ``build(cfg, spec, n_app,
                                  cut)`` that returns the plain workflow
                                  (`bench.workflows`' form)
    bench/e2e/<metric>.py         an end-to-end metric's `read(ctx)`
    bench/layers/<metric>.py      a per-layer metric's `read(ctx)`

so a cell, a mix, a driver, a workflow family or a metric is added with
files and entries alone.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def layer_path(metric: str) -> Path:
    return BENCH / "layers" / f"{metric}.py"


def e2e_path(metric: str) -> Path:
    return BENCH / "e2e" / f"{metric}.py"


def driver_path(name: str) -> Path:
    return BENCH / "drivers" / f"{name}.py"


def family_path(name: str) -> Path:
    return BENCH / "families" / f"{name}.py"


class Cell:
    """One workload entry with its configuration, mix and metrics."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = json.loads(
            (root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            traffic_path(self.entry["traffic"]).read_text())
        self.chips = self.entry["chips"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def _load(path: Path):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts)
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The `read(ctx)` function of one per-layer metric."""
    return _load(layer_path(metric)).read


def load_e2e(metric: str):
    """The `read(ctx)` function of one end-to-end metric."""
    return _load(e2e_path(metric)).read


def load_driver(name: str):
    """The `Driver` class that serves a mix."""
    return _load(driver_path(name)).Driver


@functools.lru_cache(maxsize=None)
def load_family(name: str):
    """The `build(cfg, spec, n_app, cut)` function of a workflow family,
    loaded once: the generator builds workflows inside the window."""
    return _load(family_path(name)).build
