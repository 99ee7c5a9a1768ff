"""BENCHMARK.json against the benchmark's contract, and every name it
holds resolved to its file."""
from __future__ import annotations

import json
import math
import re
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchlib import spec  # noqa: E402

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LINE_RE = re.compile(r"^[^\n\t]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_top_level_keys_and_command(bench):
    assert set(bench) == TOP_KEYS
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    cmd = bench["command"]
    assert 1 <= len(cmd) <= 32 and all(LINE_RE.match(w) for w in cmd)
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


@pytest.mark.parametrize("kind", sorted(ENTRY_KEYS))
def test_entries_keys_names_and_units(bench, kind):
    allowed = ENTRY_KEYS[kind] | ({"workloads"} if kind in (
        "end_to_end", "per_layer") else set())
    names = [e["name"] for e in bench[kind]]
    assert len(names) == len(set(names))
    for e in bench[kind]:
        assert ENTRY_KEYS[kind] <= set(e) <= allowed, e
        assert spec.NAME_RE.match(e["name"]), e["name"]
        if "unit" in e:
            assert spec.UNIT_RE.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in SOURCES
        for key in ("why", "layer", "source"):
            if key in e:
                assert LINE_RE.match(e[key]), (key, e[key])


def test_configs_resolve(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert spec.config_path(c, ROOT).is_file()
        assert spec.reference_path(c, ROOT).is_file()
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert spec.NAME_RE.match(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)",
                                 key), f"{key} is a width"


def test_workloads_resolve_to_config_traffic_driver_and_limits(bench):
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        four += w["chips"] == 4
        cell = spec.Cell(w["name"], ROOT, bench)
        assert spec.traffic_path(w["traffic"], BENCH).is_file()
        assert spec.driver_path(cell.traffic["driver"], BENCH).is_file()
        assert hasattr(cell.driver(), "run")
        limits = spec.load_json(BENCH / "limits" / f"{w['name']}.json")
        assert all(math.isfinite(v) and v > 0
                   for v in limits["limits"].values())
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics_resolve_and_every_cell_reports_enough(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert hasattr(spec.load_module(spec.metric_path(m["name"], BENCH),
                                        "bench_metric"), "read")
        for w in m.get("workloads", []):
            assert w in cells
            moved = e2e[m["moves"]]
            assert "workloads" not in moved or w in moved["workloads"]
    for w in cells:
        reported = [m["name"] for m in spec.metrics_for(bench, "end_to_end",
                                                        w)]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_for(bench, "per_layer", w)


def test_a_cell_mix_and_metric_are_added_by_new_files_only(bench, tmp_path):
    """A new traffic mix, a new cell and a new per-layer metric: new
    files and new BENCHMARK.json entries, with no file edited."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p.relative_to(tmp_path): p.read_bytes()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    base = bench["workloads"][0]
    cell0 = spec.Cell(base["name"], ROOT, bench)
    new_mix = dict(cell0.traffic, clients=cell0.traffic["clients"] * 2)
    (tmp_path / "bench" / "traffic" / "doubled.json").write_text(
        json.dumps(new_mix))
    (tmp_path / "bench" / "metrics" / "rounds_seen.py").write_text(
        "def read(summary, work, peaks):\n    return work.get('rounds')\n")
    new_name = base["config"] + ".doubled"
    (tmp_path / "bench" / "limits" / f"{new_name}.json").write_text(
        (BENCH / "limits" / f"{base['name']}.json").read_text())
    grown = json.loads(json.dumps(bench))
    grown["workloads"].append(dict(base, name=new_name, traffic="doubled"))
    grown["per_layer"].append({
        "name": "rounds_seen", "unit": "rounds", "better": "higher",
        "source": "device_trace", "layer": "meta step",
        "moves": bench["end_to_end"][0]["name"], "workloads": [new_name]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(grown))

    cell = spec.Cell(new_name, tmp_path)
    assert cell.traffic["clients"] == new_mix["clients"]
    assert cell.config == cell0.config
    assert "rounds_seen" in cell.readers()
    assert cell.readers()["rounds_seen"].read(None, {"rounds": 3}, {}) == 3
    after = {p.relative_to(tmp_path): p.read_bytes()
             for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and p.relative_to(tmp_path) in before}
    assert after == before
