"""The paper-round cell at a tiny size on the CPU: a sound run is correct
under the committed limits, every fault the cell can have comes out not
correct, and the control reads far above a sound run.

The harness's look for a chip is skipped (the CPU's device is handed to
the run); everything else of a run is driven as on the chip, with the
trainer's step broken underneath where a fault is planted."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

import tiny  # noqa: E402
from benchlib import compare, spec, trace  # noqa: E402

import run as bench_run  # noqa: E402

CELL = "femnist-cnn.cohort32"
SEED = 2**31 + 4099


def context(cell):
    import jax
    return bench_run.RunContext(
        workload=cell.name, config=cell.config, traffic=cell.traffic,
        reference=cell.reference(), limits=bench_run.limits_for(CELL),
        seed=SEED, seconds=0.05, devices=jax.devices()[:1],
        t0=time.perf_counter(), tracer=trace.Tracer(None), quiet=True)


@pytest.fixture(scope="module")
def cell():
    return tiny.tiny_cell(CELL)


@pytest.fixture(scope="module")
def sound(cell):
    driver = cell.driver()
    return driver.run(context(cell))


def test_sound_run_is_correct(cell, sound):
    import jax
    assert compare.passed(sound["checks"]), sound["checks"]
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    line = bench_run.result_line(cell, sound, jax.devices()[:1], False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"round_s", "setup_s"}


def test_control_in_lower_precision_reads_above_a_sound_run(cell, sound):
    """The reference at "high" (three bfloat16 passes) in the program's
    place. At this size its gaps (about 5e-6 on the first gradient) stay
    under the limits, which are set from the chip at the cell's own size,
    where the control reads 8.4e-4 and more; here it has to read well
    above a sound run of the same size."""
    driver = cell.driver()
    ctx = context(cell)
    session = driver.Session(ctx)
    session.close()
    control = driver.follow(ctx, session, "control")
    checks = compare.training_checks(control, sound["readings"]["reference"],
                                     ctx.limits)
    sound_gaps = {c["name"]: c["value"] for c in sound["checks"]}
    assert checks[1]["name"] == "grad_norm_gap"
    assert checks[1]["value"] > 10 * sound_gaps["grad_norm_gap"], \
        (checks, sound_gaps)


def unchanged(step):
    import jax
    return jax.jit(lambda st, *args: (st, step(st, *args)[1]))


def half_batch(step):
    """Half of the round's writers left out; the rest re-weighted."""
    import jax

    def cut(x):
        return x[:x.shape[0] // 2]

    def broken(st, sup, qry, w):
        w = cut(w)
        return step(st, jax.tree.map(cut, sup), jax.tree.map(cut, qry),
                    w / w.sum())
    return broken


def wrong_answer(step):
    """The query loss and gradient taken on the support set."""
    return lambda st, sup, qry, w: step(st, sup, sup, w)


@pytest.mark.parametrize("fault", [unchanged, half_batch, wrong_answer])
def test_fault_in_the_timed_path_is_not_correct(cell, fault, monkeypatch):
    driver = cell.driver()
    real = driver.build_trainer

    def broken(*args, **kw):
        tr = real(*args, **kw)
        real_init = tr.init

        def init(key, model_init):
            state = real_init(key, model_init)
            tr._step = fault(tr._step)
            return state
        tr.init = init
        return tr

    monkeypatch.setattr(driver, "build_trainer", broken)
    res = driver.run(context(cell))
    assert not compare.passed(res["checks"]), res["checks"]


def test_flops_and_bytes_match_a_hand_count(cell):
    """28x28 images, 5x5 convs to 4 and 8 channels, dense 392 -> 32 -> 62;
    4 writers x (4 + 4) images."""
    ref = cell.reference()
    fwd = 2 * (28 * 28 * 4 * 25 + 14 * 14 * 8 * 25 * 4 + 392 * 32 + 32 * 62)
    assert ref.forward_flops_per_image(cell.config) == fwd
    assert ref.fomaml_flops_per_round(cell.config, cell.traffic) == \
        3 * 32 * fwd
    n = (25 * 4 + 4) + (25 * 4 * 8 + 8) + (392 * 32 + 32) + (32 * 62 + 62)
    assert ref.num_params(cell.config) == n
    assert ref.kernel_bytes(cell.config, cell.traffic) == {
        "inner_update": 3 * 4 * n * 4, "aggregate": 5 * n * 4,
        "adam": 7 * n * 4}


def test_the_published_width_is_the_papers():
    """6,603,710 parameters at hidden 2048, as the program's model says."""
    cell = spec.Cell(CELL)
    assert cell.reference().num_params(cell.config) == 6_603_710
