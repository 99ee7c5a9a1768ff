"""The trace reduction and the per-layer readers: exact arithmetic on a
hand-made profile, and sanity on small traces recorded on a TPU v5e
(`fixtures/`, made by `record_trace_fixture.py`)."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib import chips, spec, trace  # noqa: E402

FIXTURES = BENCH / "tests" / "fixtures"
PEAKS = chips.peaks_for("TPU v5 lite")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def profile():
    """Window 1000..11000 ns; chip 0 runs modules 2000..5000 and
    6000..9000, with a while (2000..5000) holding two fusions."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 1000, 11000),
        ev("bench.put_batch", 1000, 2000),
        ev("bench.wait", 5000, 6000),
        ev("unrelated", 0, 500)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(1)", 2000, 5000),
                                       ev("jit_step(1)", 6000, 9000)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = (f32[2]{0}) while(...)", 2000, 5000),
            ev("%fusion.2 = f32[8,128]{1,0} fusion(f32[8]{0} %p)", 2000,
               3000),
            ev("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)", 3000, 4500),
            ev("%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p), "
               'custom_call_target="tpu_custom_call"', 6000, 9000)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), host, dev])


def test_reduce_profile_arithmetic():
    s = trace.reduce_profile(profile())
    assert s.window_s == pytest.approx(10e-6)
    assert s.chips == 1
    assert s.busy_s == pytest.approx(6e-6)
    assert s.modules[0] == [pytest.approx((1e-6, 4e-6)),
                            pytest.approx((5e-6, 8e-6))]
    selfs = s.op_self_seconds()
    assert selfs["while.1 while (f32[2])"] == pytest.approx(0.5e-6)
    assert selfs["fusion.2 fusion f32[8,128]"] == pytest.approx(1e-6)
    assert selfs["custom-call.4 tpu_custom_call f32[8]"] == \
        pytest.approx(3e-6)
    # busy equals the top-level operations' total: self times add up
    assert sum(selfs.values()) == pytest.approx(s.busy_s)
    gaps = trace.idle_gaps(s)
    assert gaps == {"bench.put_batch": pytest.approx(1e-6),
                    "bench.wait": pytest.approx(1e-6),
                    "outside spans": pytest.approx(2e-6)}
    b = trace.breakdown(s, top=2)
    assert [n for n, _ in b["device_ops"]] == [
        "custom-call.4 tpu_custom_call f32[8]", "fusion.3 fusion f32[8]"]
    assert b["idle_gaps"][0] == ["outside spans", pytest.approx(2e-6)]


def test_reduce_profile_needs_window_and_chip():
    p = profile()
    p.planes[1].lines[0].events = []
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce_profile(p)
    p = profile()
    p.planes.pop()
    with pytest.raises(ValueError, match="TPU"):
        trace.reduce_profile(p)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        chips.peaks_for("TPU v0 imaginary")


def readers():
    bench = spec.load_benchmark()
    return {m["name"]: spec.load_module(spec.metric_path(m["name"]),
                                        "bench_metric")
            for m in bench["per_layer"]}


def test_readers_on_the_hand_made_profile():
    s = trace.reduce_profile(profile())
    work = {"driver": "lm_train", "rounds": 2, "flops_per_round": 1e6,
            "window_s": s.window_s}
    r = readers()
    assert r["mfu.lm_train"].read(s, work, PEAKS) == pytest.approx(
        100 * 2e6 / (10e-6 * PEAKS["bf16_flops_per_s"]))
    assert r["device_idle_share.lm_train"].read(s, work, PEAKS) == \
        pytest.approx(40.0)
    # a reader with nothing to read returns nothing
    for name, mod in r.items():
        assert mod.read(s, {"driver": "none"}, PEAKS) is None, name


FIXTURE_NAMES = sorted(p.name[:-len(".xplane.pb")]
                       for p in FIXTURES.glob("*.xplane.pb"))


@pytest.mark.parametrize("name", FIXTURE_NAMES)
def test_recorded_chip_trace(name):
    path = FIXTURES / f"{name}.xplane.pb"
    assert path.stat().st_size < 1 << 20
    s = _load(path)
    work = json.loads((FIXTURES / f"{name}.work.json").read_text())
    assert s.chips >= 1 and s.window_s > 0
    assert 0 < s.busy_s <= s.window_s
    selfs = s.op_self_seconds()
    assert all(v >= -1e-9 for v in selfs.values())
    assert sum(selfs.values()) <= s.busy_s * s.chips * (1 + 1e-6)
    gaps = trace.idle_gaps(s)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s,
                                               rel=1e-6, abs=1e-9)
    values = {n: m.read(s, work, PEAKS) for n, m in readers().items()}
    read = {n: v for n, v in values.items() if v is not None}
    assert read, "no reader found anything in a recorded trace"
    for n, v in read.items():
        # at a test's size a kernel's few hundred kB sit in on-chip memory,
        # so an HBM roofline does not bound it; at the cell's size it does
        assert 0 <= v <= (math.inf if n.endswith("_roofline") else 100), \
            (n, v)
    if "meta_update_roofline" in read:
        mod = readers()["meta_update_roofline"]
        kernels = [(mod.kernel_of(op.name), op.end - op.start)
                   for op in s.ops if mod.kernel_of(op.name)]
        assert {k for k, _ in kernels} == {"inner_update", "aggregate",
                                          "adam"}
        least = sum(work["kernel_bytes"][k] for k, _ in kernels) / \
            PEAKS["hbm_bytes_per_s"]
        assert read["meta_update_roofline"] == pytest.approx(
            100 * least / sum(d for _, d in kernels))


def _load(path: Path):
    from jax.profiler import ProfileData
    return trace.reduce_profile(ProfileData.from_file(str(path)))


def test_lm_readers_on_a_recorded_chip_trace():
    """The LM cell's readers are the same reductions; a recorded LM step
    is over 1 MB even at a test's size (its operation names carry every
    shape), so they are checked on the paper-round recording."""
    s = _load(FIXTURES / "femnist-cnn.cohort32.xplane.pb")
    work = {"driver": "lm_train", "rounds": 3, "flops_per_round": 1e9}
    r = readers()
    assert r["mfu.lm_train"].read(s, work, PEAKS) == pytest.approx(
        100 * 3e9 / (s.window_s * s.chips * PEAKS["bf16_flops_per_s"]))
    assert r["device_idle_share.lm_train"].read(s, work, PEAKS) == \
        pytest.approx(100 * (1 - s.busy_s / s.window_s))
    assert 0 < r["device_idle_share.lm_train"].read(s, work, PEAKS) < 100
