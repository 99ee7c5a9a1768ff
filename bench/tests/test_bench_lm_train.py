"""The LM training cell at a tiny size on the CPU: the control and every
fault the cell can have come out not correct under the committed limits
(set from chip readings at the cell's own size), and read above a sound
run of the same size.

The harness's look for a chip is skipped (the CPU's device is handed to
the run); everything else of a run is driven as on the chip, with the
timed path broken underneath where a fault is planted."""
from __future__ import annotations

import functools
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
sys.path.insert(0, str(BENCH.parent / "src"))

import tiny  # noqa: E402
from benchlib import compare, generators, seeds  # noqa: E402

import run as bench_run  # noqa: E402

CELL = "smollm-360m.train4k"
SEED = 2**31 + 977          # past 32 signed bits, as check seeds may be


def run_once(cell, **kw):
    import jax
    devs = jax.devices()[:cell.chips]
    res, _ = bench_run.run_cell(cell, devs, seed=SEED, seconds=0.05,
                                traced=False, t0=time.perf_counter(), **kw)
    return res


@pytest.fixture(scope="module")
def cell():
    return tiny.tiny_cell(CELL)


@pytest.fixture(scope="module")
def sound(cell):
    return run_once(cell)


def test_sound_run_result_line(cell, sound):
    """At this size bfloat16 round-off moves a few times more of a small
    leaf than at the cell's own, so the line is checked, not `correct`."""
    import jax
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    line = bench_run.result_line(cell, sound, jax.devices()[:1], False)
    assert line["correct"] is compare.passed(sound["checks"])
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"loss_gap", "grad_norm_gap",
                                   "update_norm_gap"}
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0


def test_control_in_lower_precision_is_not_correct(cell, sound):
    """The reference in float8 in the program's place."""
    import jax
    ref = cell.reference()
    n = cell.traffic["checked_rounds"]
    host = generators.lm_task_batches(seeds.traffic_rng(SEED), cell.traffic,
                                      cell.config["vocab_size"])
    theta0 = jax.device_get(jax.jit(functools.partial(
        ref.init_params, cfg=cell.config))(seeds.weight_key_data(SEED)))
    want = sound["readings"]["reference"]
    control = ref.Reference(cell.config, "fp8").run(theta0, host[:n], n)
    checks = compare.training_checks(control, want,
                                     bench_run.limits_for(CELL))
    assert not compare.passed(checks), checks
    sound_gaps = {c["name"]: c["value"] for c in sound["checks"]}
    assert any(c["value"] > 3 * sound_gaps[c["name"]] for c in checks)


def unchanged(step, build):
    import jax
    return jax.jit(lambda s, b: (s, step(s, b)[1]))


def half_batch(step, build):
    """Half of each round's clients left out; the mean over the rest."""
    import dataclasses

    import jax
    half = build(lambda sh: dataclasses.replace(
        sh, clients_per_round=sh.clients_per_round // 2,
        global_batch=sh.global_batch // 2))

    def cut(b):
        return jax.tree.map(lambda x: x[:, :x.shape[1] // 2], b)
    return jax.jit(lambda s, b: half(s, cut(b)))


def wrong_answer(step, build):
    """The query loss and gradient taken on the support set."""
    import jax
    return jax.jit(lambda s, b: step(s, {"support": b["support"],
                                         "query": b["support"]}))


@pytest.mark.parametrize("fault", [unchanged, half_batch, wrong_answer])
def test_fault_in_the_timed_path_is_not_correct(cell, sound, fault,
                                                monkeypatch):
    driver = cell.driver()
    real = driver.build_program

    def broken(cfg, mcfg, shape, mesh):
        step, init = real(cfg, mcfg, shape, mesh)

        def build(reshape):
            return real(cfg, mcfg, reshape(shape), mesh)[0]
        return fault(step, build), init

    monkeypatch.setattr(driver, "build_program", broken)
    res = run_once(cell)
    assert not compare.passed(res["checks"]), res["checks"]
    sound_gaps = {c["name"]: c["value"] for c in sound["checks"]}
    assert any(c["value"] > 3 * sound_gaps[c["name"]] for c in res["checks"])


def test_flops_match_a_hand_count(cell):
    """d 64, 4 heads of 16 over 2 kv heads, d_ff 128, 2 layers, vocab
    256; 4 clients x (1 + 1) sequences of 32 tokens."""
    ref = cell.reference()
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert ref.matmul_params(cell.config) == 2 * per_layer + 256 * 64
    seq = 6 * (2 * per_layer + 256 * 64) * 32 + 6 * 2 * 32 * 32 * 4 * 16
    assert ref.train_flops_per_sequence(cell.config, 32) == seq
    assert ref.fomaml_flops_per_round(cell.config, 4, 1, 1, 32) == 8 * seq


def test_run_without_a_chip_exits_nonzero_with_no_result():
    import subprocess
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        cwd=str(BENCH.parent))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "needs a TPU" in proc.stderr
