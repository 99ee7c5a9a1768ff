"""Jitted wrapper / dispatcher for attention.

Layout contract with the models: (B, L, H, hd) activations. The Pallas
kernel wants (B, H, L, hd); this wrapper transposes around the call.

impl:
  "xla"              — pure-jnp reference (CPU tests, dry-run lowering)
  "pallas_interpret" — Pallas kernel, interpret mode (CPU correctness)
  "pallas"           — Pallas kernel compiled for TPU (production)
Unset, the platform picks (``kernels/dispatch.py``). The kernels have
a first-order backward (``flash_bwd_dq``, ``flash_bwd_dkv``, under a
custom VJP), so training and adaptation differentiate through them.
A gradient that is differentiated again (second-order MAML, Meta-SGD)
cannot go through a ``pallas_call``: inside ``dispatch.second_order``
this family runs "xla".
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.attention import ref
from repro.kernels.attention.flash_attention import flash_attention_bhld
from repro.kernels.dispatch import ImplChoice

_IMPL = ImplChoice("attention", first_order_only=True)
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_offset: int = 0, kv_length=None, impl: str | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    scale: float | None = None):
    """q: (B, Lq, H, hd); k: (B, Lk, Kv, hd); v: (B, Lk, Kv, hd_v) ->
    (B, Lq, H, hd_v). The softmax scale defaults to 1/sqrt(hd); the
    kernels' blocks to ``flash_attention.block_sizes``."""
    impl = resolve_impl(impl)
    if impl == "xla" or kv_length is not None:
        # variable kv_length (ragged decode) stays on the XLA path
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, kv_length=kv_length,
                                 scale=scale)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhld(
        qt, kt, vt, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, scale=scale,
        interpret=(impl == "pallas_interpret"))
    return jnp.swapaxes(out, 1, 2)
