"""The kernel-impl rule shared by every family (kernels/dispatch.py):
explicit impl > scoped pin > the platform ("pallas" on TPU, "xla"
elsewhere), with no environment variable in the way."""
import jax
import pytest

from repro.kernels import dispatch
from repro.kernels.attention import ops as attn_ops
from repro.kernels.decode_attention import ops as dec_ops
from repro.kernels.meta_update import ops as mu_ops
from repro.kernels.ssd import ops as ssd_ops

FAMILIES = {"meta_update": mu_ops, "attention": attn_ops,
            "decode_attention": dec_ops, "ssd": ssd_ops}


def test_platform_picks_xla_off_tpu():
    assert jax.default_backend() != "tpu"
    assert dispatch.platform_impl() == "xla"


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_resolution_order(family, monkeypatch):
    ops = FAMILIES[family]
    assert ops.resolve_impl() == "xla"
    with ops.use_impl("pallas_interpret"):
        assert ops.resolve_impl() == "pallas_interpret"
        assert ops.resolve_impl("xla") == "xla"          # explicit wins
        for other, o in FAMILIES.items():                # pins are per family
            if other != family:
                assert o.resolve_impl() == "xla"
    assert ops.resolve_impl() == "xla"
    # the platform is read at call time, not at import
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_impl() == "pallas"
    with ops.use_impl("xla"):
        assert ops.resolve_impl() == "xla"


def test_unknown_impl_rejected():
    with pytest.raises(ValueError):
        mu_ops.resolve_impl("cuda")
    with pytest.raises(ValueError):
        with attn_ops.use_impl("triton"):
            pass
    assert attn_ops.resolve_impl() == "xla"


def test_second_order_scope_keeps_first_order_kernels_on_xla():
    """Inside dispatch.second_order a family with a first-order backward
    only (attention) resolves to XLA over a pin or the platform; an
    explicit impl still wins, and the other families keep their rule."""
    with attn_ops.use_impl("pallas_interpret"), \
            mu_ops.use_impl("pallas_interpret"):
        with dispatch.second_order():
            assert attn_ops.resolve_impl() == "xla"
            assert attn_ops.resolve_impl("pallas_interpret") == \
                "pallas_interpret"
            assert mu_ops.resolve_impl() == "pallas_interpret"
        assert attn_ops.resolve_impl() == "pallas_interpret"


def _lm(arch):
    from repro.configs import get_config, reduced_config
    from repro.launch.steps import make_apply_fn
    from repro.models import init_lm
    cfg = reduced_config(get_config(arch))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size)
    return make_apply_fn(cfg, remat=True), params, tokens


def _close(got, want, rtol):
    import numpy as np
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.linalg.norm(g - w) <= rtol * max(np.linalg.norm(w), 1e-6)


@pytest.mark.parametrize("arch", ["smollm-360m", "deepseek-v2-lite"])
def test_lm_loss_grad_through_flash_kernels(arch):
    """The LM loss's forward is differentiated through the flash
    kernels' custom VJP (make_apply_fn pins no attention impl): its
    gradient under the kernels equals its gradient under XLA, for GQA
    and for MLA with YaRN's softmax scale."""
    from repro.core.losses import lm_loss
    apply_fn, params, tokens = _lm(arch)
    loss_fn, _ = lm_loss(apply_fn)
    grad = jax.jit(jax.value_and_grad(loss_fn))
    with attn_ops.use_impl("xla"):
        want = grad(params, tokens)
    with attn_ops.use_impl("pallas_interpret"):
        got = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
        jaxpr = str(jax.make_jaxpr(jax.grad(loss_fn))(params, tokens))
    assert "flash_bwd_dkv" in jaxpr and "flash_bwd_dq" in jaxpr
    _close(got, want, 1e-5)


def test_second_order_maml_keeps_xla_attention():
    """Second-order MAML differentiates the inner gradient again, which
    the kernels' backward cannot serve: its meta-gradient through the
    same apply runs on XLA attention under a kernel pin, and equals the
    meta-gradient under XLA."""
    from repro.core.algorithms import make_algorithm
    from repro.core.losses import lm_loss
    apply_fn, params, tokens = _lm("smollm-360m")
    algo = make_algorithm("maml", *lm_loss(apply_fn), inner_lr=0.01)
    phi = {"theta": params}
    step = lambda: jax.jit(algo.client_grad)(phi, tokens[:1], tokens[1:])
    with attn_ops.use_impl("xla"):
        want = step()
    with attn_ops.use_impl("pallas_interpret"):
        got = step()
        jaxpr = str(jax.make_jaxpr(algo.client_grad)(phi, tokens[:1],
                                                     tokens[1:]))
    assert "flash_" not in jaxpr
    _close(got, want, 0.0)
