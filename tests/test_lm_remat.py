"""The LM layer scan's remat policy: the backward keeps the flash
forward's output and log-sum-exp instead of running ``flash_fwd`` again,
the gradients stay those of the unrematerialized forward, and on the
second-order (XLA attention) path the policy saves nothing, as a plain
``jax.checkpoint`` does. Reduced smollm-360m (a scanned stack) and
reduced deepseek-v2-lite (an unrolled dense lead layer, then the stack)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import get_config, reduced_config
from repro.kernels import dispatch
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention.flash_attention import RESIDUAL_LSE, RESIDUAL_O
from repro.launch.steps import make_apply_fn, make_train_step
from repro.models import init_lm

ARCHS = ("smollm-360m", "deepseek-v2-lite")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
NAMES = (RESIDUAL_O, RESIDUAL_LSE)


def _setup(arch):
    cfg = reduced_config(get_config(arch))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


def _grad_fn(cfg, tokens, remat):
    apply = make_apply_fn(cfg, remat=remat)

    def loss(p):
        logits = apply(p, tokens)[0]
        return jnp.mean(jax.nn.log_softmax(logits)[..., 0])
    return jax.grad(loss)


def _sites(cfg) -> int:
    """Attention calls in one forward's program: each unrolled lead
    layer, and the scanned period's layers once (the scan body)."""
    return cfg.first_k_dense + 1


def _kernel_calls(closed) -> dict:
    """Pallas calls by kernel name, each equation that makes one counted
    where it stands (a scan body once), nested programs included."""
    counts = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                counts[getattr(name, "name", name)] += 1
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    if isinstance(sub, ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, Jaxpr):
                        walk(sub)
    walk(closed.jaxpr)
    return {k: counts[k] for k in KERNELS}


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("program", ["grad", "fomaml_step"])
@pytest.mark.parametrize("arch", ARCHS)
def test_remat_runs_flash_fwd_once_per_site(arch, program):
    """Each pass's backward through the rematerialized scan calls
    ``flash_fwd`` once per attention site (the forward's), not twice
    (the forward's and the remat's recompute); the backward kernels
    once each, as before. One pass: the loss's gradient; two: a FOMAML
    step (support, then query)."""
    cfg, params, tokens = _setup(arch)
    with attn_ops.use_impl("pallas_interpret"):
        if program == "grad":
            closed, passes = jax.make_jaxpr(
                _grad_fn(cfg, tokens, True))(params), 1
        else:
            step, init_state, _, _ = make_train_step(
                cfg, algo_name="fomaml", inner_lr=0.05, outer_lr=1e-3)
            part = {"tokens": tokens[None, :, None]}     # (G, C, S, L)
            closed, passes = jax.make_jaxpr(step)(
                init_state(jax.random.PRNGKey(0)),
                {"support": part, "query": part}), 2
    want = dict.fromkeys(KERNELS, _sites(cfg) * passes)
    assert _kernel_calls(closed) == want


@pytest.mark.parametrize("arch", ARCHS)
def test_remat_gradients_match_no_remat(arch):
    """Saving the residuals changes where values come from, not what
    they are: the gradients equal the unrematerialized forward's."""
    cfg, params, tokens = _setup(arch)
    with attn_ops.use_impl("pallas_interpret"):
        got = jax.jit(_grad_fn(cfg, tokens, True))(params)
        want = jax.jit(_grad_fn(cfg, tokens, False))(params)
    tol = 1e-5 if jnp.dtype(cfg.dtype) == jnp.float32 else 6e-3
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        if float(jnp.linalg.norm(w)) > 0:
            assert _rel(g, w) < tol, (jax.tree_util.keystr(path), _rel(g, w))


@pytest.mark.parametrize("arch", ARCHS)
def test_second_order_remat_saves_nothing(arch, monkeypatch):
    """Under ``dispatch.second_order`` attention runs XLA: the program
    holds none of the residual names (the Pallas path holds both), and
    its gradients equal, bit for bit, those of the scan under a plain
    ``jax.checkpoint`` with no policy."""
    cfg, params, tokens = _setup(arch)
    with attn_ops.use_impl("pallas_interpret"):
        kernels = str(jax.make_jaxpr(_grad_fn(cfg, tokens, True))(params))
    assert all(n in kernels for n in NAMES)
    with dispatch.second_order():
        grad = _grad_fn(cfg, tokens, True)
        text = str(jax.make_jaxpr(grad)(params))
        got = jax.jit(grad)(params)
    assert not any(n in text for n in NAMES)
    assert "flash_fwd" not in text

    plain = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint",
                        lambda f, policy=None, **kw: plain(f, **kw))
    with dispatch.second_order():
        want = jax.jit(_grad_fn(cfg, tokens, True))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
