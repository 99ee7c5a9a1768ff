"""Dispatcher for one-token decode attention.

impl: "xla" (oracle), "pallas", "pallas_interpret"; unset, the platform
picks (``kernels/dispatch.py``). ``use_impl`` scopes a pin — baked in at
*trace* time: wrap the first call of a jitted serve fn, not later
replays of an already-compiled executable.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.decode_attention import ref
from repro.kernels.decode_attention.flash_decode import flash_decode
from repro.kernels.dispatch import ImplChoice

_IMPL = ImplChoice("decode_attention")
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use


def decode_attention(q, k_cache, v_cache, kv_length, *, impl=None,
                     block_k: int = 512):
    """q: (B, H, hd); caches: (B, C, Kv, hd); kv_length: () or (B,)."""
    impl = resolve_impl(impl)
    kvl = jnp.broadcast_to(jnp.asarray(kv_length), (q.shape[0],))
    if impl == "xla":
        return ref.decode_attention_ref(q, k_cache, v_cache, kvl)
    return flash_decode(q, k_cache, v_cache, kvl, block_k=block_k,
                        interpret=(impl == "pallas_interpret"))
