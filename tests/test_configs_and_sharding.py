"""Config exactness vs the assigned architecture table, sharding-rule
invariants, EP-MoE numerical equivalence, and launch-path lowering."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import INPUT_SHAPES, get_config, list_archs, reduced_config

# (layers, d_model, heads, kv, d_ff, vocab, experts, topk) per assignment
ASSIGNED = {
    "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536, 16, 2),
    "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768, 8, 2),
    "granite-3-2b": (40, 2048, 32, 8, 8192, 49155, 0, 0),
    "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206, 0, 0),
    "deepseek-v2-236b": (60, 5120, 128, 128, 1536, 102400, 160, 6),
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064, 0, 0),
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280, 0, 0),
    "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936, 0, 0),
    "smollm-360m": (32, 960, 15, 5, 2560, 49152, 0, 0),
    "nemotron-4-340b": (96, 18432, 96, 8, 73728, 256000, 0, 0),
}


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_config_exactness(arch):
    cfg = get_config(arch)
    L, d, H, Kv, ff, V, E, K = ASSIGNED[arch]
    assert cfg.num_layers == L and cfg.d_model == d
    assert cfg.num_heads == H and cfg.num_kv_heads == Kv
    assert cfg.d_ff == ff and cfg.vocab_size == V
    assert cfg.num_experts == E and cfg.num_experts_per_tok == K
    assert cfg.source, "every config must cite its source"


def test_assigned_extras():
    assert get_config("deepseek-v2-236b").kv_lora_rank == 512
    assert get_config("deepseek-v2-236b").num_shared_experts == 2
    assert get_config("mamba2-370m").ssm_state == 128
    assert get_config("mixtral-8x22b").sliding_window is not None
    assert get_config("qwen2-vl-7b").mrope and get_config("qwen2-vl-7b").qkv_bias
    assert get_config("qwen2.5-3b").qkv_bias
    assert get_config("nemotron-4-340b").mlp_act == "relu2"
    assert get_config("seamless-m4t-medium").is_encoder_decoder
    jamba = get_config("jamba-v0.1-52b")
    assert jamba.layer_pattern.count("attn") == 1    # 1:7 interleave
    assert len(jamba.layer_pattern) == 8


def test_sharding_rules_divisibility_guard():
    """Dims that don't divide the mesh axis stay replicated."""
    from repro.launch.mesh import make_device_mesh
    from repro.sharding.rules import param_pspecs
    mesh = make_device_mesh(jax.devices()[:1])
    params = {"wq": jnp.zeros((960, 960)),       # 960 % 1 == 0 -> sharded
              "embed": jnp.zeros((7, 960))}
    specs = param_pspecs(params, mesh)
    assert specs["wq"] is not None
    # on a 1-device mesh everything divides; use a synthetic big mesh via
    # dryrun tests instead — here just verify structure matches
    assert set(specs.keys()) == {"wq", "embed"}


def test_ep_moe_matches_tp_single_device(rng):
    """On a 1-device mesh the EP all_to_all is the identity, so EP and TP
    MoE must agree numerically (same routing, same capacity)."""
    from repro.launch.mesh import make_device_mesh
    from repro.models import moe as tp_moe
    from repro.models.layers import Rng
    from repro.sharding.ep_moe import ep_moe_apply
    cfg = dataclasses.replace(
        reduced_config(get_config("mixtral-8x22b")), num_shared_experts=0)
    mesh = make_device_mesh(jax.devices()[:1])
    params = tp_moe.moe_init(Rng(jax.random.PRNGKey(0)), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(0, 0.5, (2, 8, cfg.d_model)), jnp.float32)
    y_tp, _aux = tp_moe.moe_apply(params, cfg, x)
    y_ep = ep_moe_apply(params, cfg, x, mesh)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_tp),
                               rtol=2e-4, atol=2e-4)


def test_ep_moe_softmax_gating_matches_tp_single_device(rng):
    """A DeepSeek-gated config with moe_impl "ep" and a mesh set takes
    the all-to-all path, not the held-expert layer, and gates it the
    DeepSeek way: on a 1-device mesh it matches the TP capacity
    dispatch under the same gating, and not under Mixtral's."""
    from repro.launch.mesh import make_device_mesh
    from repro.models import blocks
    from repro.models import moe as tp_moe
    from repro.models.layers import Rng
    from repro.sharding.context import set_mesh
    cfg = dataclasses.replace(
        reduced_config(get_config("deepseek-v2-236b")), num_shared_experts=0,
        moe_impl="ep")
    assert cfg.router_scoring == "softmax"
    params = tp_moe.moe_init(Rng(jax.random.PRNGKey(0)), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(0, 0.5, (2, 8, cfg.d_model)), jnp.float32)
    y_tp, _aux = tp_moe.moe_apply(params, cfg, x)
    set_mesh(make_device_mesh(jax.devices()[:1]))
    try:
        y_ep, _aux, stats = blocks._moe(params, cfg, x)
    finally:
        set_mesh(None)
    assert stats == {}
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_tp),
                               rtol=2e-4, atol=2e-4)
    y_mixtral, _aux = tp_moe.moe_apply(
        params, dataclasses.replace(cfg, router_scoring="topk_softmax"), x)
    assert not np.allclose(np.asarray(y_mixtral), np.asarray(y_tp),
                           rtol=2e-2, atol=2e-2)


def test_held_experts_exclude_the_expert_all_to_all():
    with pytest.raises(AssertionError, match="all-to-all"):
        dataclasses.replace(get_config("deepseek-v2-lite"), experts_held=8,
                            moe_impl="ep")


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_launch_path_lowers_on_host_mesh(kind, rng):
    """input_specs + step builders lower on the 1-device host mesh for a
    reduced config (guards the production launch path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_device_mesh
    from repro.launch.steps import (input_specs, make_decode_step,
                                    make_prefill_step, make_train_step)
    from repro.models import init_lm
    from repro.sharding.rules import param_pspecs, state_pspecs
    cfg = reduced_config(get_config("qwen2.5-3b"))
    mesh = make_device_mesh(jax.devices()[:1])
    shape = dataclasses.replace(
        INPUT_SHAPES[{"train": "train_4k", "prefill": "prefill_32k",
                      "decode": "decode_32k"}[kind]],
        seq_len=32, global_batch=4,
        **({"clients_per_round": 2, "seqs_per_client": 2}
           if kind == "train" else {}))
    nm = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        if kind == "train":
            step, init_state, _, _ = make_train_step(cfg)
            state = jax.eval_shape(lambda: init_state(jax.random.PRNGKey(0)))
            spec = input_specs(cfg, shape, mesh)
            pspec = param_pspecs(state["phi"]["theta"], mesh)
            fn = jax.jit(step, in_shardings=(
                nm(state_pspecs(state, pspec, mesh)), nm(spec["pspec"])))
            lowered = fn.lower(state, spec["batch"])
        elif kind == "prefill":
            spec = input_specs(cfg, shape, mesh)
            params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
            fn = jax.jit(make_prefill_step(cfg),
                         in_shardings=(nm(param_pspecs(params, mesh)),
                                       nm(spec["pspec"])))
            lowered = fn.lower(params, spec["batch"])
        else:
            spec = input_specs(cfg, shape, mesh)
            scfg = spec["serving_cfg"]
            params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), scfg))
            fn = jax.jit(make_decode_step(scfg),
                         in_shardings=(nm(param_pspecs(params, mesh)),
                                       nm(spec["pspec"]["cache"]),
                                       nm(spec["pspec"]["tokens"])))
            lowered = fn.lower(params, spec["batch"]["cache"],
                               spec["batch"]["tokens"])
        assert lowered.compile() is not None
