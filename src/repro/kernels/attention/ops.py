"""Jitted wrapper / dispatcher for attention.

Layout contract with the models: (B, L, H, hd) activations. The Pallas
kernel wants (B, H, L, hd); this wrapper transposes around the call.

impl:
  "xla"              — pure-jnp reference (CPU tests, dry-run lowering)
  "pallas_interpret" — Pallas kernel, interpret mode (CPU correctness)
  "pallas"           — Pallas kernel compiled for TPU (production)
Unset, the platform picks (``kernels/dispatch.py``). The kernel has no
backward: a caller that differentiates through attention pins "xla"
with ``use_impl`` (the LM loss does, ``launch/steps.make_apply_fn``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.attention import ref
from repro.kernels.attention.flash_attention import flash_attention_bhld
from repro.kernels.dispatch import ImplChoice

_IMPL = ImplChoice("attention")
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    q_offset: int = 0, kv_length=None, impl: str | None = None,
                    block_q: int = 128, block_k: int = 128,
                    scale: float | None = None):
    """q: (B, Lq, H, hd); k, v: (B, Lk, Kv, hd) -> (B, Lq, H, hd). The
    softmax scale defaults to 1/sqrt(hd)."""
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
