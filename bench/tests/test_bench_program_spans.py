"""The program-span readers: exact arithmetic of `idle_by_span` on
hand-made intervals, None wherever the program's spans or counter are
not there to read, and the split of a small chip trace recorded with
the program's spans (`fixtures/femnist-cnn.cohort32.spans.*`, made by
`record_trace_fixture.py` and renamed)."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import chips, program_spans, spec, trace  # noqa: E402

import test_bench_trace  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"
SPANS_FIXTURE = "femnist-cnn.cohort32.spans"
PARENT_FIXTURE = "femnist-cnn.cohort32"
PEAKS = chips.peaks_for("TPU v5 lite")
NEW = ("stage_idle_share.femnist", "dispatch_idle_share.femnist",
       "run_overhead_idle_share.femnist", "compiles_in_window.femnist",
       "compiles_in_window.lm_train")
FEMNIST_WORK = {"driver": "paper_rounds", "rounds": 2, "flops_per_round": 1.0,
                "kernel_bytes": {"inner_update": 1, "aggregate": 1,
                                 "adam": 1}}


def readers():
    bench = spec.load_benchmark()
    return {m["name"]: spec.load_module(spec.metric_path(m["name"]),
                                        "bench_metric")
            for m in bench["per_layer"] if m["name"] in NEW}


def test_idle_by_span_splits_a_gap_across_two_spans():
    # busy 0..1 and 3..4 of a 5 s window: idle 1..3 and 4..5
    spans = [("a", 0.5, 2.0), ("b", 2.0, 3.5)]
    got = program_spans.idle_by_span({0: [(0.0, 1.0), (3.0, 4.0)]}, spans,
                                     5.0)
    assert got == {"a": pytest.approx(1.0), "b": pytest.approx(1.0),
                   None: pytest.approx(1.0)}


def test_idle_by_span_takes_the_innermost_span():
    spans = [("run", 0.0, 10.0, {}), ("round", 1.0, 9.0, {}),
             ("stage", 1.0, 3.0, {}), ("dispatch", 3.0, 4.0, {}),
             ("flush", 4.0, 9.0, {})]
    # idle 0..2 (run, then stage), 5..6 (flush), 9..10 (run)
    got = program_spans.idle_by_span({0: [(2.0, 5.0), (6.0, 9.0)]}, spans,
                                     10.0)
    assert got == {"run": pytest.approx(2.0), "stage": pytest.approx(1.0),
                   "flush": pytest.approx(1.0)}


def test_idle_by_span_outside_every_span_and_over_chips():
    spans = [("a", 2.0, 3.0)]
    # chip 0 idle 0..4 (1 s inside a), chip 1 idle 3..4: averaged
    got = program_spans.idle_by_span({0: [(4.0, 5.0)], 1: [(0.0, 3.0),
                                                           (4.0, 5.0)]},
                                     spans, 5.0)
    assert got == {None: pytest.approx(2.0), "a": pytest.approx(0.5)}
    assert program_spans.idle_by_span({0: []}, [], 2.0) == \
        {None: pytest.approx(2.0)}


def test_new_readers_read_nothing_on_the_hand_made_profile():
    import repro.utils.trace  # noqa: F401  the counter is loaded
    s = trace.reduce_profile(test_bench_trace.profile())
    for work in (FEMNIST_WORK, {"driver": "lm_train", "rounds": 1}):
        for name, mod in readers().items():
            assert mod.read(s, work, PEAKS) is None, name


def _recorded(name, tmp_path):
    """The fixture's summary, with its trace as the newest under a
    stand-in for `bench/out/`."""
    out = tmp_path / "out" / f"trace-{name}"
    out.mkdir(parents=True)
    shutil.copy(FIXTURES / f"{name}.xplane.pb", out / "run.xplane.pb")
    work = json.loads((FIXTURES / f"{name}.work.json").read_text())
    return test_bench_trace._load(FIXTURES / f"{name}.xplane.pb"), work


def test_new_readers_read_nothing_without_the_programs_spans(
        tmp_path, monkeypatch):
    """A trace of a program without the spans, or with the counter not
    loaded, reads None; so does the committed fixture where `bench/out`
    holds no trace of its window."""
    import repro.utils.trace  # noqa: F401
    s, work = _recorded(PARENT_FIXTURE, tmp_path)
    r = readers()
    for name, mod in r.items():
        assert mod.read(s, work, PEAKS) is None, name
    monkeypatch.setattr(program_spans, "OUT_DIR", tmp_path / "out")
    for name in ("stage_idle_share.femnist", "dispatch_idle_share.femnist",
                 "run_overhead_idle_share.femnist"):
        assert r[name].read(s, work, PEAKS) is None, name
    assert r["compiles_in_window.femnist"].read(s, work, PEAKS) == 0
    monkeypatch.setattr(program_spans, "COUNTER_MODULE", "no.such.counter")
    assert r["compiles_in_window.femnist"].read(s, work, PEAKS) is None


def test_a_stale_trace_reads_nothing(tmp_path, monkeypatch):
    import dataclasses
    s, work = _recorded(SPANS_FIXTURE, tmp_path)
    monkeypatch.setattr(program_spans, "OUT_DIR", tmp_path / "out")
    other = dataclasses.replace(s, window_s=s.window_s + 1e-9)
    for name, mod in readers().items():
        assert mod.read(other, work, PEAKS) is None, name


def test_recorded_trace_splits_the_idle_share(tmp_path, monkeypatch):
    """The four femnist readers read the recorded trace; with the idle
    time outside `fedmeta.run` the three shares make up the idle share,
    and set-up left nothing to compile inside the window."""
    import repro.utils.trace  # noqa: F401
    assert (FIXTURES / f"{SPANS_FIXTURE}.xplane.pb").stat().st_size < 1 << 20
    s, work = _recorded(SPANS_FIXTURE, tmp_path)
    monkeypatch.setattr(program_spans, "OUT_DIR", tmp_path / "out")
    r = readers()
    got = {n: m.read(s, work, PEAKS) for n, m in r.items()}
    assert got["compiles_in_window.lm_train"] is None
    assert got["compiles_in_window.femnist"] == 0
    shares = [got[n] for n in NEW[:3]]
    assert all(v is not None and 0 <= v <= 100 for v in shares), got
    split = program_spans.idle_split(s)
    idle = 100 * (1 - s.busy_s / s.window_s)
    assert sum(shares) + split["outside"] == pytest.approx(idle, abs=1e-9)
    assert shares[0] > 0 and shares[2] > 0   # staging, the loss drain
    spans = program_spans.load(s).spans
    names = {n for n, *_ in spans}
    assert {"fedmeta.run", "fedmeta.round", "fedmeta.round.stage",
            "fedmeta.round.sample", "fedmeta.round.put",
            "fedmeta.round.dispatch", "fedmeta.round.flush"} <= names
    rounds = sorted(st["round"] for n, _, _, st in spans
                    if n == "fedmeta.round")
    assert len(rounds) == work["rounds"]
