"""Pallas TPU flash-decode kernel: one-token attention over a KV cache.

Decode is latency-bound on cache reads (§Perf H1); this kernel is the
VMEM-streamed counterpart of the serve path:
  - grid (B, Kv, nc): cache length is the innermost (sequential) axis,
    (m, l, acc) online-softmax carries live in VMEM scratch — the cache
    streams HBM->VMEM exactly once, in bf16, with the f32 upcast done
    per-tile in registers (the XLA path materializes an f32 cache copy),
  - GQA packing: all G = H/Kv query heads of one kv head are processed
    together as a (G, hd) tile — one cache read serves G heads
    (MXU matmul (G, hd) x (hd, bk)),
  - kv_length masks invalid slots (ring caches are position-free; see
    models/attention.py gqa_decode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_decode_kernel(kvl_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, bk: int, nc: int,
                         scale: float):
    ci = pl.program_id(2)
    kvl = kvl_ref[pl.program_id(0)]                  # () valid length

    @pl.when(ci == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)              # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = ci * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kvl, s, NEG_INF)

    m_prev = m_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    l_scr[...] = jnp.broadcast_to(
        alpha * l_scr[...][:, :1] + jnp.sum(p, axis=-1, keepdims=True),
        l_scr.shape)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ci == nc - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...][:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, kv_length, *, block_k: int = 512,
                 interpret: bool = False):
    """q: (B, H, hd); caches: (B, C, Kv, hd); kv_length: (B,) int32.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    _, C, Kv, _ = k_cache.shape
    assert H % Kv == 0
    G = H // Kv
    bk = min(block_k, C)
    assert C % bk == 0, (C, bk)
    nc = C // bk
    # layouts: q -> (B, Kv, G, hd); caches -> (B, Kv, C, hd)
    qt = q.reshape(B, Kv, G, hd)
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)

    kernel = functools.partial(_flash_decode_kernel, bk=bk, nc=nc,
                               scale=1.0 / (hd ** 0.5))
    # kv_length rides in SMEM as a scalar-prefetch operand (the index
    # maps receive it after the grid indices): a rank-1 VMEM block of
    # one int per batch row is not a legal TPU tile once B > 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Kv, nc),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, c, kvl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, c, kvl: (b, h, c, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, c, kvl: (b, h, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, h, c, kvl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
        interpret=interpret,
    )(kv_length.astype(jnp.int32), qt, kt, vt)
    return out.reshape(B, H, hd)
