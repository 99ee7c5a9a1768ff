"""Pallas TPU flash attention, forward and backward (tiled online softmax).

TPU-native design (targets v5e; validated with interpret=True on CPU):
  - inputs pre-transposed to (B, H, L, hd) so the last two dims tile
    cleanly onto (sublane, lane),
  - the MXU takes the operands in their dtype (bfloat16 in the model)
    and accumulates in float32; the softmax statistics stay float32,
  - GQA folded into the k/v BlockSpec index_map (h -> h // group_size),
    no materialized kv repeat,
  - causal + sliding-window masks from absolute positions (q_offset
    supports decode/chunked prefill); a tile the mask covers whole is
    skipped, and its index_map repeats the last live block, so it costs
    no DMA either; only tiles the mask cuts build a mask.

Three kernels, named for the profiler:
  flash_fwd      grid (B, H, nq, nk), kv innermost: the (m, l, acc)
                 online-softmax carry lives in VMEM; writes o and the
                 per-row log-sum-exp (the backward's residual)
  flash_bwd_dq   grid (B, H, nq, nk), kv innermost, dq accumulated in VMEM
  flash_bwd_dkv  grid (B, Kv, nk, G * nq): the group's query heads and q
                 blocks innermost, dk and dv accumulated in VMEM, so GQA
                 sums over its group inside the kernel

`flash_attention_bhld` joins them under a custom VJP. Its backward is
first order: a step that differentiates the loss twice (MAML,
Meta-SGD) runs attention on XLA (``kernels/dispatch.second_order``).

The forward names its two residuals with ``checkpoint_name``: the
output (``RESIDUAL_O``) and the log-sum-exp (``RESIDUAL_LSE``). A
rematerialized layer whose policy saves these names (the LM layer
scan's) keeps them instead of running ``flash_fwd`` again in its
backward, and recomputes only q, k and v, which cost a norm, the
projections and RoPE. Outside such a policy the names change nothing.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128

# The forward's residuals as a remat policy sees them (module docstring)
RESIDUAL_O = "flash_attention.o"
RESIDUAL_LSE = "flash_attention.lse"

# Rows of q and of k/v a grid step takes, at most. The chip sweep in
# PERF.md §6 (blocks 128-1024 for each kernel, at hd 64 and at hd 192 /
# hd_v 128) found 1024 x 1024 best or within 1.1% of best for all three
# kernels at both widths: the larger the score tile, the fewer grid
# steps and re-reads, and its f32 temporaries (4 MiB each) fit VMEM.
MAX_BLOCK = 1024


def _block(length: int) -> int:
    """The largest of MAX_BLOCK, MAX_BLOCK/2, ... down to 128 that
    divides length; the whole length where it is at most MAX_BLOCK or
    nothing divides it."""
    if length <= MAX_BLOCK:
        return length
    b = MAX_BLOCK
    while b >= LANES:
        if length % b == 0:
            return b
        b //= 2
    return length


def block_sizes(lq: int, lk: int) -> tuple[int, int]:
    """(bq, bk) of all three kernels for these lengths."""
    return _block(lq), _block(lk)


@dataclasses.dataclass(frozen=True)
class Attn:
    """What every kernel of one attention call shares (static)."""
    scale: float
    causal: bool
    window: int | None
    q_offset: int
    block_q: int
    block_k: int
    interpret: bool = False

    def k_range(self, qi, bq, bk, nk):
        """First and last kv block that q block `qi` attends to."""
        first, last = 0, nk - 1
        if self.causal:
            last = jnp.minimum(last, (self.q_offset + (qi + 1) * bq - 1) // bk)
        if self.window is not None:
            lo = self.q_offset + qi * bq - self.window + 1
            first = jnp.maximum(lo, 0) // bk
        return first, last

    def q_range(self, kj, bq, bk, nq):
        """First and last q block that attends to kv block `kj`."""
        first, last = 0, nq - 1
        if self.causal:
            first = jnp.maximum(kj * bk - self.q_offset, 0) // bq
        if self.window is not None:
            hi = kj * bk + bk - 2 + self.window - self.q_offset
            last = jnp.minimum(last, jnp.maximum(hi, -1) // bq)
        return first, last

    @property
    def masked(self) -> bool:
        return self.causal or self.window is not None

    def mask(self, qpos, kpos):
        keep = jnp.ones(qpos.shape, jnp.bool_)
        if self.causal:
            keep = keep & (kpos <= qpos)
        if self.window is not None:
            keep = keep & (kpos > qpos - self.window)
        return keep

    def cut(self, q0, bq, k0, bk):
        """Whether the mask cuts the (q0.., k0..) tile, i.e. the tile
        is neither whole nor empty (rows and columns are absolute)."""
        if not self.masked:
            return False
        cut = False
        if self.causal:   # some key of the tile lies past some query
            cut = cut | (k0 + bk - 1 > q0)
        if self.window is not None:   # some key lies at or before q - window
            cut = cut | (k0 <= q0 + bq - 1 - self.window)
        return cut


# every kernel's grid: three parallel axes, the accumulating one last
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _clamp(x, lo, hi, n: int):
    """Block index x held to the live range [lo, hi], and to [0, n)."""
    return jnp.clip(jnp.clip(x, lo, hi), 0, n - 1)


def _lanes(x, n: int):
    """A (rows, 128) lane-replicated column -> (rows, n)."""
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES)) if n != LANES else x
    return x[:, :1]


def _column(row):
    """A (1, n) row -> (n, 128), each row's value in every lane."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, row.shape[-1])))


def _tiles(a: Attn, live, cut, body):
    """Run `body(masked)` on a live tile: with the mask where the mask
    cuts it, without where the tile is whole."""
    if not a.masked:
        body(False)
        return

    @pl.when(live & cut)
    def _masked():
        body(True)

    @pl.when(live & jnp.logical_not(cut))
    def _whole():
        body(False)


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, a: Attn, bq: int, bk: int, nk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = a.k_range(qi, bq, bk, nk)
    q0, k0 = a.q_offset + qi * bq, ki * bk

    def body(masked: bool):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * a.scale
        if masked:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(a.mask(qpos, kpos), s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]           # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        l_scr[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(a, (ki >= first) & (ki <= last), a.cut(q0, bq, k0, bk), body)

    @pl.when(ki == nk - 1)
    def _done():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.transpose(m_scr[...] + jnp.log(l))[:1]


def flash_fwd(q, k, v, a: Attn):
    """q (B, H, Lq, hd), k (B, Kv, Lk, hd), v (B, Kv, Lk, hd_v) ->
    o (B, H, Lq, hd_v) in q's dtype, lse (B, H, 1, Lq) float32."""
    B, H, Lq, hd = q.shape
    _, Kv, Lk, _ = k.shape
    hd_v = v.shape[-1]
    G = H // Kv
    bq, bk = min(a.block_q, Lq), min(a.block_k, Lk)
    assert H % Kv == 0 and Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    nq, nk = Lq // bq, Lk // bk

    def kv_map(b, h, i, j):
        first, last = a.k_range(i, bq, bk, nk)
        return b, h // G, _clamp(j, first, last, nk), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, a=a, bq=bq, bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd_v), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd_v), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Lq, hd_v), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Lq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # running max (replicated)
            pltpu.VMEM((bq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, hd_v), jnp.float32),    # output accumulator
        ],
        compiler_params=_PARAMS,
        interpret=a.interpret,
        name="flash_fwd",
    )(q, k, v)


# ----------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               lse_scr, delta_scr, acc_scr, *, a: Attn, bq: int, bk: int,
               nk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        lse_scr[...] = _column(lse_ref[0, 0])
        delta_scr[...] = _column(delta_ref[0, 0])
        acc_scr[...] = jnp.zeros_like(acc_scr)

    first, last = a.k_range(qi, bq, bk, nk)
    q0, k0 = a.q_offset + qi * bq, ki * bk

    def body(masked: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * a.scale
        if masked:
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(a.mask(qpos, kpos), s, NEG_INF)
        p = jnp.exp(s - _lanes(lse_scr[...], bk))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta_scr[...], bk))
        acc_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(a, (ki >= first) & (ki <= last), a.cut(q0, bq, k0, bk), body)

    @pl.when(ki == nk - 1)
    def _done():
        dq_ref[0, 0] = (acc_scr[...] * a.scale).astype(dq_ref.dtype)


def flash_bwd_dq(q, k, v, do, lse, delta, a: Attn):
    """-> dq (B, H, Lq, hd) in q's dtype. lse, delta: (B, H, 1, Lq) f32."""
    B, H, Lq, hd = q.shape
    _, Kv, Lk, _ = k.shape
    hd_v = v.shape[-1]
    G = H // Kv
    bq, bk = min(a.block_q, Lq), min(a.block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    nq, nk = Lq // bq, Lk // bk

    def kv_map(b, h, i, j):
        first, last = a.k_range(i, bq, bk, nk)
        return b, h // G, _clamp(j, first, last, nk), 0

    row = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i))
    return pl.pallas_call(
        functools.partial(_dq_kernel, a=a, bq=bq, bk=bk, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd_v), kv_map),
            pl.BlockSpec((1, 1, bq, hd_v), lambda b, h, i, j: (b, h, i, 0)),
            row, row,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),   # lse as a column
            pltpu.VMEM((bq, LANES), jnp.float32),   # delta as a column
            pltpu.VMEM((bq, hd), jnp.float32),      # dq accumulator
        ],
        compiler_params=_PARAMS,
        interpret=a.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, a: Attn, bq: int, bk: int,
                nq: int, G: int):
    kj, t = pl.program_id(2), pl.program_id(3)
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    first, last = a.q_range(kj, bq, bk, nq)
    q0, k0 = a.q_offset + qi * bq, kj * bk

    def body(masked: bool):
        q, k, v, do = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0]
        # the tile transposed: kv rows, q columns, so lse and delta are
        # rows (broadcast over sublanes) and no operand is transposed
        s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * a.scale
        if masked:
            kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            s = jnp.where(a.mask(qpos, kpos), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0])                       # (bk, bq)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0])
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tiles(a, (qi >= first) & (qi <= last), a.cut(q0, bq, k0, bk), body)

    @pl.when(t == G * nq - 1)
    def _done():
        dk_ref[0, 0] = (dk_scr[...] * a.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_bwd_dkv(q, k, v, do, lse, delta, a: Attn):
    """-> (dk (B, Kv, Lk, hd), dv (B, Kv, Lk, hd_v)) in k's and v's
    dtypes, each summed over the kv head's group of query heads."""
    B, H, Lq, hd = q.shape
    _, Kv, Lk, _ = k.shape
    hd_v = v.shape[-1]
    G = H // Kv
    bq, bk = min(a.block_q, Lq), min(a.block_k, Lk)
    assert Lq % bq == 0 and Lk % bk == 0, (Lq, bq, Lk, bk)
    nq, nk = Lq // bq, Lk // bk

    def q_block(j, t):
        first, last = a.q_range(j, bq, bk, nq)
        return _clamp(t % nq, first, last, nq)

    def q_map(b, kv, j, t):
        return b, kv * G + t // nq, q_block(j, t), 0

    def row_map(b, kv, j, t):
        return b, kv * G + t // nq, 0, q_block(j, t)

    kv_map = lambda b, kv, j, t: (b, kv, j, 0)
    row = pl.BlockSpec((1, 1, 1, bq), row_map)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, a=a, bq=bq, bk=bk, nq=nq, G=G),
        grid=(B, Kv, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), q_map),
            pl.BlockSpec((1, 1, bk, hd), kv_map),
            pl.BlockSpec((1, 1, bk, hd_v), kv_map),
            pl.BlockSpec((1, 1, bq, hd_v), q_map),
            row, row,
        ],
        out_specs=[pl.BlockSpec((1, 1, bk, hd), kv_map),
                   pl.BlockSpec((1, 1, bk, hd_v), kv_map)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd_v), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=a.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)


# --------------------------------------------------------------- custom VJP

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention(q, k, v, a: Attn):
    return flash_fwd(q, k, v, a)[0]


def _attention_fwd(q, k, v, a):
    o, lse = flash_fwd(q, k, v, a)
    o = checkpoint_name(o, RESIDUAL_O)
    lse = checkpoint_name(lse, RESIDUAL_LSE)
    return o, (q, k, v, o, lse)


def _attention_bwd(a, res, do):
    q, k, v, o, lse = res
    do = do.astype(o.dtype)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None, :]
    dq = flash_bwd_dq(q, k, v, do, lse, delta, a)
    dk, dv = flash_bwd_dkv(q, k, v, do, lse, delta, a)
    return dq, dk, dv


_attention.defvjp(_attention_fwd, _attention_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret", "scale"))
def flash_attention_bhld(q, k, v, *, causal: bool = True, window=None,
                         q_offset: int = 0, block_q: int | None = None,
                         block_k: int | None = None, interpret: bool = False,
                         scale: float | None = None):
    """q: (B, H, Lq, hd); k: (B, Kv, Lk, hd); v: (B, Kv, Lk, hd_v).
    Returns (B, H, Lq, hd_v) — hd_v may differ from hd (MLA). The
    softmax scale defaults to 1/sqrt(hd); the blocks of all three
    kernels to `block_sizes`.
    Differentiable (first order) in q, k and v."""
    _, _, Lq, hd = q.shape
    bq, bk = block_sizes(Lq, k.shape[2])
    a = Attn(scale=float(1.0 / (hd ** 0.5) if scale is None else scale),
             causal=causal, window=window, q_offset=q_offset,
             block_q=block_q or bq, block_k=block_k or bk,
             interpret=interpret)
    return _attention(q, k, v, a)
