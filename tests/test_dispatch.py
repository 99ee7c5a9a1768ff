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


def test_lm_loss_pins_xla_attention():
    """The LM loss forward is differentiated, and the flash-attention
    kernel has no backward: make_apply_fn pins XLA attention even when
    the platform (or an outer pin) would pick the kernel."""
    import jax.numpy as jnp

    from repro.configs import get_config, reduced_config
    from repro.launch.steps import make_apply_fn
    from repro.models import init_lm
    cfg = reduced_config(get_config("smollm-360m"))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    apply_fn = make_apply_fn(cfg, remat=False)

    def loss(p):
        return jnp.mean(apply_fn(p, tokens)[0].astype(jnp.float32))

    with attn_ops.use_impl("pallas_interpret"):
        g = jax.grad(loss)(params)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(g))
