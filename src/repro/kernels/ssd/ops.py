"""Jitted wrapper for the SSD scan: Pallas intra-chunk kernel + XLA
inter-chunk recurrence, with the pure-jnp chunked oracle as fallback.

impl: "xla", "pallas", "pallas_interpret"; unset, the platform picks
(``kernels/dispatch.py``). The kernel has no backward: a caller that
differentiates through the scan pins "xla" with ``use_impl`` (the LM
loss does, ``launch/steps.make_apply_fn``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import ImplChoice
from repro.kernels.ssd import ref
from repro.kernels.ssd.ssd_scan import ssd_intra_chunk_pallas

_IMPL = ImplChoice("ssd")
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use


def ssd_chunked(x, dt, A, Bm, Cm, *, chunk: int, impl: str | None = None,
                return_final_state: bool = False):
    """SSD over (B, L, nh, hp) inputs; see kernels/ssd/ref.py for shapes.

    With return_final_state, also returns the (B, nh, hp, N) state after
    the last token (for prefill -> decode handoff)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.ssd_chunked_ref(x, dt, A, Bm, Cm, chunk,
                                   return_final_state=return_final_state)

    B, L, nh, hp = x.shape
    N = Bm.shape[-1]
    nc = L // chunk
    dtf = dt.astype(jnp.float32)
    a = (dtf * A.astype(jnp.float32)[None, None, :])          # (B,L,nh)
    xdt = (x.astype(jnp.float32) * dtf[..., None])            # (B,L,nh,hp)

    # layout for the kernel: (B, nh, L, ·)
    xdt_t = jnp.moveaxis(xdt, 2, 1)                           # (B,nh,L,hp)
    a_t = jnp.moveaxis(a, 2, 1)[..., None]                    # (B,nh,L,1)
    y_intra, S = ssd_intra_chunk_pallas(
        xdt_t, a_t, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
        chunk=chunk, interpret=(impl == "pallas_interpret"))
    # S: (B, nh, nc, N, hp)

    # ---- inter-chunk recurrence (XLA; tiny state, O(nc) steps)
    cum = jnp.cumsum(a_t[..., 0].reshape(B, nh, nc, chunk), axis=-1)
    chunk_decay = jnp.exp(cum[..., -1])                       # (B,nh,nc)

    def scan_step(S_prev, inp):
        S_c, dec = inp                                        # (B,nh,N,hp),(B,nh)
        S_in = S_prev
        S_out = S_c + S_prev * dec[..., None, None]
        return S_out, S_in

    S0 = jnp.zeros((B, nh, N, hp), jnp.float32)
    S_final, S_in = jax.lax.scan(scan_step, S0,
                                 (jnp.moveaxis(S, 2, 0),
                                  jnp.moveaxis(chunk_decay, 2, 0)))
    S_in = jnp.moveaxis(S_in, 0, 2)                           # (B,nh,nc,N,hp)

    # ---- inter-chunk output: y_inter[i] = exp(cum_i) C_i . S_in
    Cm_c = Cm.astype(jnp.float32).reshape(B, nc, chunk, N)
    y_inter = jnp.einsum("bcin,bhcnp,bhci->bhcip",
                         Cm_c, S_in, jnp.exp(cum))
    y = y_intra.reshape(B, nh, nc, chunk, hp) + y_inter
    y = jnp.moveaxis(y.reshape(B, nh, L, hp), 1, 2).astype(x.dtype)
    if return_final_state:
        return y, jnp.swapaxes(S_final, -1, -2)               # (B,nh,hp,N)
    return y


def ssd_decode_step(state, x, dt, A, Bm, Cm):
    """Single-token recurrent update.

    state: (B, nh, hp, N); x: (B, nh, hp); dt: (B, nh); Bm/Cm: (B, N).
    Returns (y (B, nh, hp), new_state).
    """
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A.astype(jnp.float32)[None])
    state = (state * decay[..., None, None]
             + jnp.einsum("bh,bn,bhp->bhpn", dtf, Bm.astype(jnp.float32),
                          x.astype(jnp.float32)))
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), state)
    return y.astype(x.dtype), state
