"""The DeepSeek-style LM training cell at a tiny size on the CPU: a sound
run reads correct under the committed limits (set from chip readings at
the cell's own size), the control and every planted fault read not
correct, and the routing counters and per-layer readers read the run.
The tiny configuration is float32 (bfloat16 round-off at widths of 64
reads above the limits set at 2048), so its reference rounds nothing
and its control is the reference in bfloat16.

The harness's look for a chip is skipped (the CPU's device is handed to
the run); everything else of a run is driven as on the chip, with the
timed path broken underneath where a fault is planted."""
from __future__ import annotations

import dataclasses
import functools
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import compare, generators, seeds, spec  # noqa: E402

import run as bench_run  # noqa: E402

CELL = "deepseek-v2-lite.train2k"
SEED = 2**31 + 1501         # past 32 signed bits, as check seeds may be
# d 64, 4 heads of 16 + 8 rope, latent 16; dense FFN 128; 8 routed
# experts of 32 (4 held here, 2 per token) + 1 shared; 3 layers; float32
CONFIG = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
              num_attention_heads=4, num_key_value_heads=4,
              qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
              kv_lora_rank=16, num_hidden_layers=3, router_experts=8,
              n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1,
              vocab_size=256, dtype="float32")
TRAFFIC = dict(clients=4, support_seqs=1, query_seqs=1, seq_len=32,
               distinct_batches=3)


def tiny_cell() -> spec.Cell:
    cell = spec.Cell(CELL)
    cell.config = dict(cell.config, **CONFIG)
    cell.traffic = dict(cell.traffic, **TRAFFIC)
    return cell


def run_once(cell, **kw):
    import jax
    devs = jax.devices()[:cell.chips]
    res, _ = bench_run.run_cell(cell, devs, seed=SEED, seconds=0.05,
                                traced=False, t0=time.perf_counter(), **kw)
    return res


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


@pytest.fixture(scope="module")
def sound(cell):
    return run_once(cell)


def test_sound_run_is_correct(cell, sound):
    import jax
    assert sound["attempted"] >= 1 and sound["failed"] == 0
    line = bench_run.result_line(cell, sound, jax.devices()[:1], False)
    assert line["correct"] is True, line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0


def test_routing_counters_read_every_round(cell, sound):
    """Every round reports its held pairs and largest load and drops
    none; the window's sums land in `work`."""
    routing = (sound["readings"]["program"]["routing"]
               + sound["readings"]["window_routing"])
    T = TRAFFIC["clients"] * TRAFFIC["query_seqs"] * TRAFFIC["seq_len"]
    n_moe = CONFIG["num_hidden_layers"] - 1
    for r in routing:
        assert r["moe_dropped"] == 0
        assert 0 < r["moe_pairs_held"] <= n_moe * T * 2
        assert 0 < r["moe_load_max"] <= r["moe_pairs_held"]
    work = sound["work"]
    window = sound["readings"]["window_routing"]
    assert work["moe_pairs_held"] == sum(r["moe_pairs_held"] for r in window)
    assert work["moe_dropped"] == 0
    assert work["expert_call_flops"] > 0 and work["expert_call_bytes"] > 0


def test_control_in_lower_precision_is_not_correct(cell, sound):
    """The reference in the next precision below (bfloat16 for this
    float32 configuration) in the program's place."""
    import jax
    ref = cell.reference()
    n = cell.traffic["checked_rounds"]
    host = generators.lm_task_batches(seeds.traffic_rng(SEED), cell.traffic,
                                      cell.config["vocab_size"])
    theta0 = jax.device_get(jax.jit(functools.partial(
        ref.init_params, cfg=cell.config))(seeds.weight_key_data(SEED)))
    want = sound["readings"]["reference"]
    control = ref.Reference(cell.config, "bf16").run(theta0, host[:n], n)
    checks = compare.training_checks(control, want,
                                     bench_run.limits_for(CELL))
    assert not compare.passed(checks), checks
    sound_gaps = {c["name"]: c["value"] for c in sound["checks"]}
    assert any(c["value"] > 3 * sound_gaps[c["name"]] for c in checks)


def half_batch(driver, monkeypatch):
    """Half of each round's clients left out; the mean over the rest."""
    import jax
    real = driver.build_program

    def broken(cfg, mcfg, shape, mesh):
        step, init = real(cfg, mcfg, shape, mesh)
        half = real(cfg, mcfg, dataclasses.replace(
            shape, clients_per_round=shape.clients_per_round // 2,
            global_batch=shape.global_batch // 2), mesh)[0]

        def cut(b):
            return jax.tree.map(lambda x: x[:, :x.shape[1] // 2], b)
        return jax.jit(lambda s, b: half(s, cut(b))), init
    monkeypatch.setattr(driver, "build_program", broken)


def _program_config(driver, monkeypatch, **changes):
    real = driver.program_config
    monkeypatch.setattr(driver, "program_config", lambda cfg: dataclasses
                        .replace(real(cfg), **changes))


def renormalized_top_k(driver, monkeypatch):
    """Mixtral-style gates: the top-k probabilities renormalized."""
    _program_config(driver, monkeypatch, norm_topk_prob=True)


def capacity_drops(driver, monkeypatch):
    """Pairs past capacity factor 1.0 of an expert dropped (and
    counted), as a capacity dispatch would."""
    import jax.numpy as jnp
    from repro.models import moe
    real = moe.held_pairs

    def capped(cfg, expert_ids, T):
        order, counts, _ = real(cfg, expert_ids, T)
        H = cfg.held_experts
        cap = -(-T * cfg.num_experts_per_tok // cfg.num_experts)
        ends = jnp.cumsum(counts)
        row = jnp.arange(order.shape[0])
        group = jnp.searchsorted(ends, row, side="right")        # H: no expert
        start = jnp.concatenate([ends - counts, jnp.zeros((1,), ends.dtype)])
        kept = jnp.where((group < H) & (row - start[group] < cap), group, H)
        sizes = jnp.minimum(counts, cap)
        return (order[jnp.argsort(kept, stable=True)], sizes,
                jnp.sum(counts - sizes))
    monkeypatch.setattr(moe, "held_pairs", capped)


def no_yarn_mscale(driver, monkeypatch):
    """YaRN's mscale^2 left out of the softmax scale."""
    from repro.models import attention
    monkeypatch.setattr(attention, "mla_softmax_scale", lambda cfg: float(
        1.0 / np.sqrt(cfg.head_dim + cfg.rope_head_dim)))


@pytest.mark.parametrize("fault", [half_batch, renormalized_top_k,
                                   capacity_drops, no_yarn_mscale])
def test_fault_in_the_timed_path_is_not_correct(cell, sound, fault,
                                                monkeypatch):
    import jax
    fault(cell.driver(), monkeypatch)
    res = run_once(cell)
    line = bench_run.result_line(cell, res, jax.devices()[:1], False)
    assert line["correct"] is False, res["checks"]
    if fault is capacity_drops:
        assert res["failed"] == res["attempted"] and res["work"][
            "moe_dropped"] > 0
    else:
        assert not compare.passed(res["checks"]), res["checks"]
    sound_gaps = {c["name"]: c["value"] for c in sound["checks"]}
    assert any(c["value"] > 3 * sound_gaps[c["name"]] for c in res["checks"])


def test_flops_match_a_hand_count(cell):
    """Per token: attention 64·4·24 + 64·16 + 64·8 + 2·16·4·16 +
    4·16·64; dense FFN 3·64·128; per MoE layer the router 64·8, the
    shared expert 3·64·32 and the held experts at 2·4/8 = 1 pair of
    3·64·32; the head 64·256."""
    ref = cell.reference()
    attn = 64 * 4 * 24 + 64 * 16 + 64 * 8 + 2 * 16 * 4 * 16 + 4 * 16 * 64
    moe = 64 * 8 + 3 * 64 * 32 + 1.0 * 3 * 64 * 32
    params = 3 * attn + 3 * 64 * 128 + 2 * moe + 64 * 256
    assert ref.matmul_params(cell.config) == params
    seq = 6 * params * 32 + 3 * 3 * 32 * 32 * 4 * (16 + 8 + 16)
    assert ref.train_flops_per_sequence(cell.config, 32) == seq
    assert ref.fomaml_flops_per_round(cell.config, 4, 1, 1, 32) == 8 * seq
    flops, least_bytes = ref.expert_call_cost(cell.config, 10)
    assert flops == 2 * 10 * 64 * 32
    assert least_bytes == 2 * (4 * 64 * 32 + 10 * (64 + 32))


def test_readers_read_the_run(cell, sound):
    """The four new readers on a hand-made window of the sound run."""
    readers = cell.readers()
    hlo = ('%expert_gmm.3 = bf16[128,64]{1,0} custom-call(), '
           'custom_call_target="tpu_custom_call"')
    other = '%fusion.1 = f32[8]{0} fusion()'
    ops = [SimpleNamespace(name=hlo, start=0.0, end=0.002),
           SimpleNamespace(name=hlo.replace("expert_gmm.3", "expert_tgmm"),
                           start=0.002, end=0.004),
           SimpleNamespace(name=other, start=0.004, end=1.0)]
    summary = SimpleNamespace(window_s=2.0, chips=1, busy_s=1.5, ops=ops)
    work = sound["work"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least = max(work["expert_call_flops"] / 197e12,
                work["expert_call_bytes"] / 819e9)
    roof = readers["expert_gmm_roofline"].read(summary, work, peaks)
    assert roof == pytest.approx(100.0 * 2 * least / 0.004)
    mfu = readers["mfu.deepseek-v2-lite"].read(summary, work, peaks)
    assert mfu == pytest.approx(100.0 * work["flops_per_round"]
                                * work["rounds"] / (2.0 * 197e12))
    idle = readers["device_idle_share.deepseek-v2-lite"]
    assert idle.read(summary, work, peaks) == pytest.approx(25.0)
    for name, reader in readers.items():
        assert reader.read(summary, {"driver": "lm_train"}, peaks) is None
