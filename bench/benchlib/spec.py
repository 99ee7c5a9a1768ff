"""Finding a cell's files by name.

`BENCHMARK.json` at the checkout's root names every cell (`workloads`),
configuration and metric. Everything that belongs to one of them is a
file of its own, found by its name:

  bench/configs/<config>.json     the sizes as run
  bench/configs/<config>.py       its plain reference
  bench/traffic/<traffic>.json    the traffic mix; its "driver" names
  bench/drivers/<driver>.py       the general generator and loop it runs
  bench/metrics/<metric>.py       one per-layer metric's reader

So a later cell, configuration, mix or metric is new files plus new
entries in `BENCHMARK.json`, with no file here edited.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(Exception):
    """A cell, file or entry that the benchmark cannot resolve."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload named {name!r}; known: "
                    f"{[c['name'] for c in bench['workloads']]}")


def find_config(bench: dict, name: str) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return cfg
    raise SpecError(f"no config named {name!r}")


def config_path(entry: dict, root: Path = ROOT) -> Path:
    return Path(root) / entry["file"]


def reference_path(entry: dict, root: Path = ROOT) -> Path:
    """The plain reference beside the configuration's file."""
    return config_path(entry, root).with_suffix(".py")


def traffic_path(traffic: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "traffic" / f"{traffic}.json"


def driver_path(driver: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "drivers" / f"{driver}.py"


def metric_path(metric: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "metrics" / f"{metric}.py"


def load_module(path: Path, prefix: str):
    """Import a file by path (names hold '-' and '.', so not by import)."""
    path = Path(path)
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_name = prefix + "_" + re.sub(r"[^A-Za-z0-9_]", "_", path.stem)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, kind: str, cell_name: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries that apply to a cell: those
    without a `workloads` key, and those that list it."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


class Cell:
    """One workload with everything it names, loaded."""

    def __init__(self, name: str, root: Path = ROOT,
                 bench: dict | None = None):
        self.bench = bench if bench is not None else load_benchmark(root)
        self.entry = find_cell(self.bench, name)
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config_entry = find_config(self.bench, self.entry["config"])
        self.config = load_json(config_path(self.config_entry, root))
        self.traffic = load_json(traffic_path(self.entry["traffic"],
                                              Path(root) / "bench"))
        self.root = Path(root)

    def driver(self):
        return load_module(driver_path(self.traffic["driver"],
                                       self.root / "bench"), "bench_driver")

    def reference(self):
        return load_module(reference_path(self.config_entry, self.root),
                           "bench_reference")

    def end_to_end(self) -> list[dict]:
        return metrics_for(self.bench, "end_to_end", self.name)

    def per_layer(self) -> list[dict]:
        return metrics_for(self.bench, "per_layer", self.name)

    def readers(self) -> dict:
        return {m["name"]: load_module(
            metric_path(m["name"], self.root / "bench"), "bench_metric")
            for m in self.per_layer()}
