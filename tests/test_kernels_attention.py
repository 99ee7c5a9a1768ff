"""Pallas flash-attention kernels vs the pure-jnp oracle: shape/dtype
sweeps, causal + sliding-window masks, GQA group sizes, MLA-style
mismatched value dims; the custom VJP (flash_bwd_dq, flash_bwd_dkv)
against the oracle's autodiff, and the forward's log-sum-exp
residual."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.flash_attention import (Attn, block_sizes,
                                                     flash_fwd)
from repro.kernels.attention.ops import flash_attention
from repro.kernels.attention.ref import mha_reference


def _mk(rng, B, Lq, Lk, H, Kv, hd, hd_v=None, dtype=jnp.float32):
    hd_v = hd_v or hd
    q = jnp.asarray(rng.normal(0, 1, (B, Lq, H, hd)), dtype)
    k = jnp.asarray(rng.normal(0, 1, (B, Lk, Kv, hd)), dtype)
    v = jnp.asarray(rng.normal(0, 1, (B, Lk, Kv, hd_v)), dtype)
    return q, k, v


@pytest.mark.parametrize("B,L,H,Kv,hd", [
    (1, 128, 2, 2, 64),      # MHA
    (2, 256, 4, 2, 64),      # GQA 2:1
    (1, 256, 8, 1, 128),     # MQA
    (2, 128, 3, 1, 32),      # odd head count
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref_shapes(rng, B, L, H, Kv, hd, causal):
    q, k, v = _mk(rng, B, L, L, H, Kv, hd)
    ref = flash_attention(q, k, v, causal=causal, impl="xla")
    out = flash_attention(q, k, v, causal=causal, impl="pallas_interpret",
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_sliding_window(rng, window):
    q, k, v = _mk(rng, 2, 256, 256, 4, 2, 64)
    ref = flash_attention(q, k, v, causal=True, window=window, impl="xla")
    out = flash_attention(q, k, v, causal=True, window=window,
                          impl="pallas_interpret", block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16(rng):
    q, k, v = _mk(rng, 1, 128, 128, 2, 2, 64, dtype=jnp.bfloat16)
    ref = flash_attention(q, k, v, impl="xla")
    out = flash_attention(q, k, v, impl="pallas_interpret",
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_mla_value_dim(rng):
    """MLA: qk dim 80 != value dim 64."""
    q, k, v = _mk(rng, 1, 128, 128, 4, 4, 80, hd_v=64)
    ref = flash_attention(q, k, v, impl="xla")
    out = flash_attention(q, k, v, impl="pallas_interpret",
                          block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ref_decode_ring_equivalence(rng):
    """Decode path: attention over a ring cache with kv_length masking
    equals full attention over the ordered history."""
    B, L, Kv, H, hd = 1, 65, 2, 4, 32
    q, k, v = _mk(rng, B, 1, L, H, Kv, hd)
    # full history, query at the last position
    full = mha_reference(q, k, v, causal=True, q_offset=L - 1)
    # ring: any permutation of kv slots gives the same softmax result
    perm = rng.permutation(L)
    ring = mha_reference(q, k[:, perm], v[:, perm], causal=False,
                         kv_length=L)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


# (B, L, H, Kv, hd, hd_v, causal, window, scale); blocks of 64 q rows
# and 32 kv rows, so every case has several of each and causal and
# window cases skip whole tiles and cut others
VJP_CASES = {
    "mha-causal": (1, 256, 2, 2, 64, 64, True, None, None),
    "gqa-causal": (1, 256, 6, 2, 32, 32, True, None, None),
    "mqa-causal": (2, 128, 4, 1, 32, 32, True, None, None),
    "gqa-full": (1, 256, 6, 2, 32, 32, False, None, None),
    "gqa-window": (1, 256, 6, 2, 32, 32, True, 48, None),
    "mqa-window-full": (1, 256, 4, 1, 32, 32, False, 40, None),
    "mla-scale": (1, 256, 4, 4, 80, 64, True, None, 0.3),
}


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(VJP_CASES))
def test_flash_vjp_matches_ref_grad(rng, case, dtype):
    """Output and dq, dk, dv of the kernels' custom VJP equal the
    oracle's autodiff; in bf16, against the oracle in f32 from the same
    bf16 inputs, within bf16 round-off."""
    B, L, H, Kv, hd, hd_v, causal, window, scale = VJP_CASES[case]
    q, k, v = _mk(rng, B, L, L, H, Kv, hd, hd_v, dtype=dtype)
    w = jnp.asarray(rng.normal(0, 1, (B, L, H, hd_v)), jnp.float32)

    def run(impl, *qkv):
        def f(q, k, v):
            return flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, impl=impl, block_q=64,
                                   block_k=32)
        o, back = jax.vjp(f, *qkv)
        return (o,) + back(w.astype(o.dtype))

    got = run("pallas_interpret", q, k, v)
    want = run("xla", *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = 1e-5 if dtype == jnp.float32 else 6e-3
    for name, g, r in zip(("o", "dq", "dk", "dv"), got, want):
        assert g.dtype == dtype, name
        assert _rel(g, r) < tol, (name, _rel(g, r))


@pytest.mark.parametrize("causal,window,q_offset,Lk", [
    (True, None, 0, 256), (False, None, 0, 256), (True, 48, 0, 256),
    (True, None, 64, 320)], ids=["causal", "full", "window", "offset"])
def test_flash_fwd_lse_residual(rng, causal, window, q_offset, Lk):
    """The forward's residual is logsumexp over each row's unmasked,
    scaled scores."""
    B, L, H, Kv, hd = 1, 256, 4, 2, 32
    q, k, v = _mk(rng, B, L, Lk, H, Kv, hd)
    a = Attn(scale=0.2, causal=causal, window=window, q_offset=q_offset,
             block_q=64, block_k=32, interpret=True)
    t = lambda x: jnp.swapaxes(x, 1, 2)
    _, lse = flash_fwd(t(q), t(k), t(v), a)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, H // Kv, axis=2)) * 0.2
    qpos = jnp.arange(L)[:, None] + q_offset
    kpos = jnp.arange(Lk)[None, :]
    keep = jnp.ones((L, Lk), bool)
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    want = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    assert lse.shape == (B, H, 1, L)
    np.testing.assert_allclose(np.asarray(lse[:, :, 0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("L,want", [(4096, 1024), (2048, 1024),
                                    (1000, 1000), (1536, 512), (3000, 3000)])
def test_block_sizes_rule(L, want):
    """The largest block up to 1024 that divides the length (a length
    up to 1024, or one nothing divides, is one block)."""
    assert block_sizes(L, L) == (want, want)
