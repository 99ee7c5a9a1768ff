"""DeepSeek-V2-Lite's path through the program against the plain
reference beside its benchmark configuration (`bench/configs/
deepseek-v2-lite.py`), at a tiny DeepSeek-shaped size on the CPU in
float32 with seeded random weights: MLA with YaRN rope, DeepSeek gating,
the layer told which experts it holds (its shares add up to the uncut
layer), the grouped-matmul kernel, one FOMAML step, and the routing
counters."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.grouped_matmul import ops as gmm_ops
from repro.kernels.grouped_matmul import ref as gmm_ref
from repro.launch.steps import make_train_step
from repro.models import attention, moe

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path.insert(0, str(BENCH))

# d 64, 4 heads of 16 + 8 rope over a 16-wide latent; dense FFN 96;
# 8 routed experts of 32, 2 per token, 1 shared; 3 layers; vocab 128
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=16, num_hidden_layers=3, router_experts=8,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            vocab_size=128, dtype="float32")


def _load(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


REF = _load(BENCH / "configs" / "deepseek-v2-lite.py", "dsv2_lite_reference")
DRIVER = _load(BENCH / "drivers" / "lm_train_moe.py", "dsv2_lite_driver")


def mm(spec, a, b):
    """The reference's products in float32 at full precision."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def configs(held: int = 8, first: int = 0, **changes):
    """(reference config dict, program ModelConfig) of one chip's share."""
    with open(BENCH / "configs" / "deepseek-v2-lite.json") as f:
        rcfg = dict(json.load(f), **dict(TINY, n_routed_experts=held,
                                         first_held_expert=first, **changes))
    return rcfg, DRIVER.program_config(rcfg)


def weights(rcfg, seed: int = 0):
    return REF.init_params(np.array([seed, 7], np.uint32), rcfg)


def close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-12)
    assert err < tol, err


def test_yarn_scale_is_the_release_s():
    """mscale = 0.1 · 0.707 · ln 40 + 1 = 1.2608; the softmax scale is
    192^-1/2 times its square, 1.59x the plain one."""
    cfg = get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26081, abs=1e-5)
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * m * m)
    assert attention.mla_softmax_scale(dataclasses.replace(
        cfg, yarn_factor=0.0)) == pytest.approx(192 ** -0.5)


@pytest.mark.parametrize("length", [16, 64])
def test_mla_yarn_forward_matches_the_reference(length):
    rcfg, mcfg = configs()
    p = weights(rcfg)
    layer = DRIVER.to_program(p)["lead_0"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, length, 64))
    pos = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32), (2, length))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda a: attention.mla_forward(layer, mcfg, a,
                                                      pos))(x)
        want = jax.jit(lambda a: REF.attention(a, p["dense"], rcfg, mm))(x)
    close(got, want, 2e-5)


@pytest.mark.parametrize("norm,scale", [(False, 1.0), (False, 16.0),
                                        (True, 1.0)])
def test_deepseek_gating_by_hand(norm, scale):
    """Softmax over all 8 logits, the 2 largest probabilities, then
    renormalized or times the routed scaling factor."""
    _, mcfg = configs(norm_topk_prob=norm, routed_scaling_factor=scale)
    logits = np.random.default_rng(3).normal(size=(5, 8)).astype(np.float32)
    gates, experts = moe.top_k_gates(mcfg, jnp.asarray(logits))
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want_e = np.argsort(-probs, axis=-1)[:, :2]
    want_g = np.take_along_axis(probs, want_e, axis=-1)
    want_g = want_g / want_g.sum(-1, keepdims=True) if norm else \
        want_g * scale
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(gates), want_g, rtol=1e-6)


def _share_params(p_uncut, held: int, first: int):
    """The MoE layer 0 of the uncut weights, as a chip holding experts
    [first, first + held) holds it (program layout)."""
    t = DRIVER.to_program(p_uncut)["stack"]["pos0"]["ffn"]
    t = jax.tree.map(lambda x: x[0], t)
    for k in ("w_gate", "w_up", "w_down"):
        t[k] = t[k][first:first + held]
    return t


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_held_share_matches_the_reference_share(first):
    """A layer told it holds experts [first, first + 2) against the
    reference given the same share."""
    rcfg_all, _ = configs()
    p = weights(rcfg_all)
    rcfg, mcfg = configs(held=2, first=first)
    ref_p = jax.tree.map(lambda x: x[0], p["moe"])
    for k in ("e_gate", "e_up", "e_down"):
        ref_p[k] = ref_p[k][first:first + 2]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 64))
    with jax.default_matmul_precision("highest"):
        got, aux, stats = moe.held_moe_apply(
            _share_params(p, 2, first), mcfg, h)
        want, want_aux = REF.moe(h, ref_p, rcfg, mm)
    close(got, want, 2e-5)
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)
    assert int(stats["moe_dropped"]) == 0


def test_shares_add_up_to_the_uncut_layer():
    """The routed part of every share (each a layer told it holds its
    block of 2 of the 8 experts), plus the shared expert counted once,
    equals the reference's uncut layer."""
    rcfg, _ = configs()
    p = weights(rcfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 64))
    shared = None
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for first in range(0, 8, 2):
            _, mcfg = configs(held=2, first=first)
            params = _share_params(p, 2, first)
            y, _, _ = moe.held_moe_apply(params, mcfg, h)
            shared = moe.mlp_apply(params["shared"], h, "swiglu")
            total = total + (y - shared)
        want, _ = REF.moe(h, jax.tree.map(lambda x: x[0], p["moe"]), rcfg,
                          mm)
    close(total + shared, want, 2e-5)


GROUPS = {
    "ragged": [37, 0, 120, 5, 0, 64, 1, 29],
    "empty_and_single_rows": [0, 1, 0, 0, 1, 0, 0, 1],
    "all_in_one": [0, 0, 0, 256, 0, 0, 0, 0],
    "none": [0] * 8,
    "past_the_end": [3, 9, 0, 2, 0, 0, 17, 4],
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_grouped_matmul_kernel_matches_the_oracle(groups, dtype):
    """expert_gmm forward and both gradients (expert_gmm for the input,
    expert_tgmm for the weights) in interpret mode against ragged_dot
    and its gradient; rows past the groups are zero, gradients there
    too."""
    m, k, n = 256, 64, 32
    dt = jnp.dtype(dtype)
    sizes = jnp.asarray(GROUPS[groups], jnp.int32)
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k0, (m, k)).astype(dt)
    w = jax.random.normal(k1, (8, k, n)).astype(dt)
    g = jax.random.normal(k2, (m, n)).astype(dt)

    def run(impl):
        out, vjp = jax.vjp(lambda a, b: gmm_ops.gmm(a, b, sizes, impl=impl),
                           x, w)
        return (out,) + vjp(g)

    got, want = run("pallas_interpret"), run("xla")
    tol = 1e-5 if dtype == "float32" else 2e-2
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == dt
        close(a.astype(jnp.float32), b.astype(jnp.float32), tol)
    close(want[0].astype(jnp.float32),
          gmm_ref.gmm_masked_ref(x, w, sizes).astype(jnp.float32), tol)
    close(want[2].astype(jnp.float32),
          gmm_ref.tgmm_ref(x, g, sizes).astype(jnp.float32), tol)
    past = int(sizes.sum())
    assert not np.any(np.asarray(got[0][past:], np.float32))
    assert not np.any(np.asarray(got[1][past:], np.float32))


def _fomaml_meta_gradient(rcfg, p, sup, qry, alpha):
    """One FOMAML round of the reference's loss by hand: each client's
    query gradient at θ - α ∇L_S(θ), and their mean."""
    grad = jax.jit(jax.value_and_grad(lambda q, t: REF.lm_loss(q, t, rcfg,
                                                               mm)))
    acc, losses = None, []
    for c in range(sup.shape[0]):
        _, gs = grad(p, sup[c])
        theta_u = jax.tree.map(lambda t, gg: t - alpha * gg, p, gs)
        loss, gq = grad(theta_u, qry[c])
        losses.append(float(loss))
        acc = gq if acc is None else jax.tree.map(jnp.add, acc, gq)
    return (jax.tree.map(lambda a: a / sup.shape[0], acc),
            float(np.mean(losses)))


def test_one_fomaml_step_matches_the_reference():
    """The step's meta-gradient (Adam's first moment over 1 - b1) and
    query loss against the reference's, leaf by leaf."""
    rcfg, mcfg = configs(held=4, first=2)
    p = weights(rcfg, seed=11)
    step, _, _, optimizer = make_train_step(
        mcfg, algo_name="fomaml", inner_lr=0.05, outer_lr=1e-3)
    rng = np.random.default_rng(6)
    sup = jnp.asarray(rng.integers(0, 128, (2, 1, 16)), jnp.int32)
    qry = jnp.asarray(rng.integers(0, 128, (2, 1, 16)), jnp.int32)
    phi = {"theta": DRIVER.to_program(p)}
    state = {"phi": phi, "opt": optimizer.init(phi)}
    with jax.default_matmul_precision("highest"):
        want, want_loss = _fomaml_meta_gradient(rcfg, p, sup, qry, 0.05)
        new, mets = jax.jit(step)(state, {"support": {"tokens": sup[None]},
                                          "query": {"tokens": qry[None]}})
    assert float(mets["query_loss"]) == pytest.approx(want_loss, rel=1e-5)
    got = REF.flatten(DRIVER.from_program(new["opt"]["m"]["theta"]))
    for name, g in REF.flatten(want).items():
        close(np.asarray(got[name]) / 0.1, g, 1e-4)
    for k in moe.STATS:
        assert mets[k].dtype == jnp.int32
    assert int(mets["moe_dropped"]) == 0
    assert 0 < int(mets["moe_load_max"]) <= int(mets["moe_pairs_held"])


def capped_held_pairs(capacity_factor: float):
    """`moe.held_pairs` with the pairs past ceil(T k / E ·
    capacity_factor) of each held expert dropped (and counted), as a
    capacity dispatch would."""
    real = moe.held_pairs

    def capped(cfg, expert_ids, T):
        order, counts, _ = real(cfg, expert_ids, T)
        H = cfg.held_experts
        cap = math.ceil(T * cfg.num_experts_per_tok / cfg.num_experts
                        * capacity_factor)
        ends = jnp.cumsum(counts)
        row = jnp.arange(order.shape[0])
        group = jnp.searchsorted(ends, row, side="right")        # H: no expert
        start = jnp.concatenate([ends - counts, jnp.zeros((1,), ends.dtype)])
        kept = jnp.where((group < H) & (row - start[group] < cap), group, H)
        sizes = jnp.minimum(counts, cap)
        return (order[jnp.argsort(kept, stable=True)], sizes,
                jnp.sum(counts - sizes))
    return capped


def test_counters_under_a_skew_that_overflows_capacity(monkeypatch):
    """Every token prefers experts 0 and 1: dropless keeps every pair
    (the counters say so), while capacity 1.25 would drop most."""
    rcfg, mcfg = configs(held=4, first=0)
    p = weights(rcfg)
    params = _share_params(p, 4, 0)
    params["w_router"] = params["w_router"].at[:, :2].add(3.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (2, 16, 64)))
    T, K = 32, 2
    _, _, stats = moe.held_moe_apply(params, mcfg, h)
    assert int(stats["moe_dropped"]) == 0
    assert int(stats["moe_pairs_held"]) == T * K
    assert int(stats["moe_load_max"]) == T
    monkeypatch.setattr(moe, "held_pairs", capped_held_pairs(1.25))
    y, _, stats_c = moe.held_moe_apply(params, mcfg, h)
    cap = math.ceil(T * K / 8 * 1.25)
    assert int(stats_c["moe_dropped"]) == T * K - 2 * cap
    assert int(stats_c["moe_pairs_held"]) == 2 * cap
    assert bool(jnp.all(jnp.isfinite(y)))
