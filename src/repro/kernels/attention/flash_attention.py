"""Pallas TPU flash attention (tiled online-softmax).

TPU-native design (targets v5e; validated with interpret=True on CPU):
  - inputs pre-transposed to (B, H, L, hd) so the last two dims tile
    cleanly onto (sublane, lane) = (block, 128-multiple head_dim),
  - grid (B, H, nq, nk): the kv dimension is innermost, so each core
    iterates kv blocks sequentially while the (m, l, acc) online-softmax
    carry lives in VMEM scratch — one HBM read per tile, one HBM write
    per output block,
  - GQA folded into the k/v BlockSpec index_map (h -> h // group_size),
    no materialized kv repeat,
  - causal + sliding-window masks applied per tile from absolute
    positions (q_offset supports decode/chunked prefill).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window, q_offset: int,
                  bq: int, bk: int, nk: int):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (kpos > qpos - window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (bq, 128) replicated
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)     # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (bq, 1)
    p = jnp.exp(s - m_new[:, :1])                  # (bq, bk)
    l_new = alpha * l_prev[:, :1] + jnp.sum(p, axis=-1, keepdims=True)

    acc = acc_scr[...]
    acc = acc * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...][:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret", "scale"))
def flash_attention_bhld(q, k, v, *, causal: bool = True, window=None,
                         q_offset: int = 0, block_q: int = 128,
                         block_k: int = 128, interpret: bool = False,
                         scale: float | None = None):
    """q: (B, H, Lq, hd); k: (B, Kv, Lk, hd); v: (B, Kv, Lk, hd_v).
    Returns (B, H, Lq, hd_v) — hd_v may differ from hd (MLA). The
    softmax scale defaults to 1/sqrt(hd)."""
    B, H, Lq, hd = q.shape
    _, Kv, Lk, _ = k.shape
    hd_v = v.shape[-1]
    assert H % Kv == 0
    G = H // Kv
    bq = min(block_q, Lq)
    bk = min(block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    nq, nk = Lq // bq, Lk // bk
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd_v), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd_v), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Lq, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (replicated)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd_v), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
