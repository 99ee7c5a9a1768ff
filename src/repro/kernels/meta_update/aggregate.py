"""Pallas TPU kernels for fused client-gradient aggregation.

The server step reduces the (m, N) block of packed per-client
meta-gradients to the (N,) meta-gradient g = Σ_u w_u · g_u (paper A.2
weights by local data count). Per-leaf XLA emits one broadcast-multiply
plus reduce per tensor; this kernel makes one sweep over the block as
it is stored — each grid step streams a lane-dense (m, cols) block
(``fused.plane_block``) through VMEM, accumulates the weighted sum over
clients 0..m-1 in order, and writes (1, cols) of the output. Weights
live in SMEM and are read as scalars inside the client loop.

On top of the plain weighted mean, the failure plane (DESIGN.md §14)
adds three robust reductions over the same (m, N) block, each with a
pure-jnp reference oracle:

  * ``masked_mean_flat`` — dropout-masked renormalizing weighted mean:
    Σ w g / Σ w, so zero-weight (dropped) rows renormalize over the
    rows that actually arrived. An all-dropped round divides 0/0 and
    surfaces as NaN for the engine's non-finite guard to skip.
  * ``screened_aggregate_flat`` — per-row L2-norm screening: non-finite
    and dropped rows are rejected outright, rows whose norm exceeds
    ``factor ×`` the live-row median are clipped down to the threshold
    (clipping a row by c is identical to scaling its aggregation weight
    by c, so the reduce reuses the plain weighted kernel), and the
    result renormalizes over the *unclipped* live weights.
  * ``trimmed_mean_flat`` — coordinate-wise trimmed mean: per coordinate,
    drop the ``trim`` largest and ``trim`` smallest live values and
    average the rest — the classic Byzantine-robust estimator.
    Dedicated single-sweep kernel below.

Inputs come from the packed parameter plane (``utils/flat.py``): the
slab kernels (trimmed mean, int8 encode) need N a multiple of
ALIGN = 8 * 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.meta_update.fused import (LANE, SUBLANE, choose_block_rows,
                                             plane_block, sublane_rows)

# VMEM budget for the (m, block_rows, 128) slab of the trimmed mean and
# the int8 encode: ~2 MiB f32
_SLAB_BUDGET_ELEMS = 1 << 19
# columns the weighted aggregate sums at a time: a (1, 1024) f32
# accumulator row and a tile of clients stay in vector registers (on a
# v5e, 1.23 ms for the femnist plane against 1.40 ms at 512 and 2.28 ms
# at 256)
_ACC_COLS = 1024

# finite sentinel for the trimmed-mean selection sweeps: larger than any
# real gradient coordinate, but finite so dead-row sentinels can never
# poison an accumulation the way ±inf would (python float: pallas
# kernels cannot capture traced constants)
_BIG = 3.0e38


def _agg_kernel(w_ref, g_ref, out_ref):
    m, cols = g_ref.shape
    # clients are read one sublane tile at a time; the accumulator is
    # one (1, width) row in f32
    grp = sublane_rows(g_ref.dtype.itemsize)
    width = min(cols, _ACC_COLS)

    def add_rows(acc, u0, n, c0):
        slab = g_ref[pl.ds(u0, n), pl.ds(c0, width)].astype(jnp.float32)
        for j in range(n):          # clients u0 .. u0+n-1, in order
            acc = acc + w_ref[u0 + j] * slab[j:j + 1, :]
        return acc

    def column_chunk(k, carry):
        c0 = pl.multiple_of(k * width, width)
        acc = jnp.zeros((1, width), jnp.float32)
        if m // grp:
            acc = jax.lax.fori_loop(
                0, m // grp,
                lambda i, acc: add_rows(acc, pl.multiple_of(i * grp, grp),
                                        grp, c0),
                acc)
        if m % grp:                 # the last, partial tile of clients
            acc = add_rows(acc, m - m % grp, m % grp, c0)
        out_ref[:, pl.ds(c0, width)] = acc.astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, cols // width, column_chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def weighted_aggregate_flat(gs, w, *, interpret: bool = False):
    """gs: (m, N) packed client gradients, w: (m,) weights -> (N,) f32.

    Computes Σ_u w_u · gs[u] in a single pass; the caller is responsible
    for weight normalization (fedmeta normalizes once per round). The
    sum runs over clients 0..m-1 in order, in f32, for every column.
    """
    m, N = gs.shape
    c_blk, cols = plane_block(m, N, gs.dtype.itemsize, streams=2)
    if c_blk < m:                  # the reduce needs every client a step
        raise ValueError(f"{m} clients do not fit one VMEM block")
    if cols > _ACC_COLS:
        cols = cols // _ACC_COLS * _ACC_COLS

    out = pl.pallas_call(
        _agg_kernel,
        grid=(pl.cdiv(N, cols),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, cols), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, cols), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        interpret=interpret,
    )(w.astype(jnp.float32), gs)
    return out.reshape(N)


def weighted_aggregate_ref(gs, w):
    """Pure-jnp oracle: w @ gs, accumulating in f32.

    The dot runs in the block's dtype with a f32 accumulator so a
    reduced-precision (bf16) gradient block is consumed directly —
    upcasting gs first would materialize a full f32 copy of the block."""
    return jax.lax.dot_general(
        w.astype(gs.dtype), gs, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---- robust aggregation (DESIGN.md §14) ---------------------------------

def row_liveness(gs, w):
    """(m,) f32 mask of aggregatable rows: weight > 0 AND all-finite.

    The finiteness check is one fused reduce over |g| per row (a NaN or
    ±inf anywhere makes the row sum non-finite) rather than a
    materialized (m, N) isfinite mask."""
    row_mag = jnp.sum(jnp.abs(gs.astype(jnp.float32)), axis=1)
    live = jnp.isfinite(row_mag) & (w.astype(jnp.float32) > 0)
    return live.astype(jnp.float32)


def masked_mean_ref(gs, w):
    """Dropout-masked renormalizing weighted mean oracle: Σ w g / Σ w."""
    return weighted_aggregate_ref(gs, w) / jnp.sum(w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_mean_flat(gs, w, *, interpret: bool = False):
    """Kernel path of the masked mean: the fused weighted reduce plus a
    scalar renormalization (one elementwise op; XLA fuses it into the
    kernel's epilogue). Dropped rows carry w = 0 so the surviving rows'
    relative weights are preserved while their sum returns to 1."""
    return (weighted_aggregate_flat(gs, w, interpret=interpret)
            / jnp.sum(w.astype(jnp.float32)))


def screened_weights(gs, w, *, factor: float = 3.0):
    """Norm-screening as effective aggregation weights.

    Computes per-row L2 norms, rejects dead rows (zero weight or any
    non-finite coordinate), and derives a robust threshold
    τ = factor × median(live norms). Rows with ‖g‖ > τ are *clipped*:
    scaling row u by τ/‖g_u‖ is exactly scaling its weight by the same
    factor, so the screen composes with the plain weighted kernel.
    Returns ``(w_num, w_den)`` with
    ``aggregate = Σ w_num·g / Σ w_den`` — the denominator keeps the
    *unclipped* live weights so clipping shrinks an outlier's
    contribution instead of silently re-inflating the others. An
    all-dead round yields Σ w_den = 0 → NaN for the guard."""
    g32 = gs.astype(jnp.float32)
    sq = jnp.sum(g32 * g32, axis=1)
    live = jnp.isfinite(sq) & (w.astype(jnp.float32) > 0)
    norms = jnp.sqrt(sq)
    # masked lower median: dead rows sort to the top as +BIG sentinels
    ranked = jnp.sort(jnp.where(live, norms, _BIG))
    n_live = jnp.sum(live)
    med = ranked[jnp.maximum(n_live - 1, 0) // 2]
    thresh = jnp.float32(factor) * med
    clip = jnp.where(norms > thresh, thresh / norms, jnp.float32(1.0))
    w32 = w.astype(jnp.float32)
    w_num = jnp.where(live, w32 * clip, 0.0)
    w_den = jnp.where(live, w32, 0.0)
    return w_num, w_den


def _screen_rows(gs, w_num):
    # rejected rows may be NaN: 0-weight × NaN would still poison the
    # reduce, so zero the rejected rows before it
    return jnp.where((w_num > 0)[:, None], gs, jnp.zeros((), gs.dtype))


def screened_aggregate_ref(gs, w, *, factor: float = 3.0):
    """Norm-screened aggregation oracle (see ``screened_weights``)."""
    w_num, w_den = screened_weights(gs, w, factor=factor)
    return (weighted_aggregate_ref(_screen_rows(gs, w_num), w_num)
            / jnp.sum(w_den))


@functools.partial(jax.jit, static_argnames=("factor", "interpret"))
def screened_aggregate_flat(gs, w, *, factor: float = 3.0,
                            interpret: bool = False):
    """Kernel path of norm screening: the screen itself is (m,)-sized
    scalar work; the (m, N) reduce reuses the fused weighted kernel with
    the clipped effective weights."""
    w_num, w_den = screened_weights(gs, w, factor=factor)
    return (weighted_aggregate_flat(_screen_rows(gs, w_num), w_num,
                                    interpret=interpret)
            / jnp.sum(w_den))


def _trimmed_kernel(live_ref, g_ref, out_ref, x_ref, *, trim):
    """Coordinate-wise trimmed mean over the live rows of one slab.

    x_ref is a VMEM scratch copy of the slab with dead rows replaced by
    a ∓BIG sentinel. Each of the ``trim`` extraction sweeps finds the
    per-coordinate extreme across the m rows (tracking the first row
    index achieving it), adds it to the running extreme-sum, and knocks
    that row's coordinate out with the sentinel so the next sweep finds
    the next-most-extreme value. 2·trim sweeps of m rows each — still
    sequential streaming over the slab, same access pattern as the
    weighted kernel, no per-coordinate sort."""
    m = g_ref.shape[0]

    def fill(sign):
        # dead rows -> -sign*BIG: never selected as a sign-extreme
        def body(u, _):
            x_ref[u, :, :] = jnp.where(
                live_ref[u] > 0.0, g_ref[u, :, :].astype(jnp.float32),
                -sign * _BIG)
            return 0
        jax.lax.fori_loop(0, m, body, 0)

    def extract(sign):
        """Per-coordinate sum of the ``trim`` most sign-extreme live
        values; destructive on x_ref."""
        ext = jnp.zeros(out_ref.shape, jnp.float32)
        for _ in range(trim):
            def best_body(u, carry):
                bv, bu = carry
                xu = x_ref[u, :, :]
                better = (sign * xu) > (sign * bv)   # strict: first wins
                return (jnp.where(better, xu, bv),
                        jnp.where(better, u, bu))
            best, best_u = jax.lax.fori_loop(
                0, m, best_body,
                (jnp.full(out_ref.shape, -sign * _BIG, jnp.float32),
                 jnp.zeros(out_ref.shape, jnp.int32)))
            ext = ext + best

            def knock_out(u, _):
                xu = x_ref[u, :, :]
                x_ref[u, :, :] = jnp.where(best_u == u, -sign * _BIG, xu)
                return 0
            jax.lax.fori_loop(0, m, knock_out, 0)
        return ext

    def live_sum(u, acc):
        alive = live_ref[u] > 0.0
        return acc + jnp.where(alive, g_ref[u, :, :].astype(jnp.float32),
                               0.0)

    total = jax.lax.fori_loop(
        0, m, live_sum, jnp.zeros(out_ref.shape, jnp.float32))
    fill(1.0)
    top = extract(1.0)
    bot = jnp.zeros(out_ref.shape, jnp.float32)
    if trim:
        fill(-1.0)
        bot = extract(-1.0)
    n_live = jax.lax.fori_loop(
        0, m, lambda u, a: a + jnp.where(live_ref[u] > 0.0, 1.0, 0.0),
        jnp.float32(0.0))
    out_ref[...] = ((total - top - bot)
                    / (n_live - 2.0 * trim)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("trim", "interpret"))
def trimmed_mean_flat(gs, live, *, trim: int = 1, interpret: bool = False):
    """gs: (m, N) block, live: (m,) f32 liveness mask -> (N,) coordinate-
    wise trimmed mean over live rows (``row_liveness`` supplies the mask;
    pre-screening non-finite rows there keeps NaNs out of the kernel).

    Requires n_live > 2·trim at runtime — fewer live rows divide by a
    non-positive count and the non-finite guard skips the round; the
    static bound 2·trim < m is asserted here."""
    m, N = gs.shape
    assert N % (SUBLANE * LANE) == 0, N
    assert 0 <= 2 * trim < m, (trim, m)
    total_rows = N // LANE
    # slab + same-shape scratch both live in VMEM -> halve the budget
    max_rows = max(SUBLANE, _SLAB_BUDGET_ELEMS // (LANE * max(2 * m, 1)))
    rows = choose_block_rows(total_rows, max_rows=max_rows)
    n_tiles = total_rows // rows

    out = pl.pallas_call(
        functools.partial(_trimmed_kernel, trim=trim),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, rows, LANE), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((total_rows, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((m, rows, LANE), jnp.float32)],
        interpret=interpret,
    )(live.astype(jnp.float32), gs.reshape(m, total_rows, LANE))
    return out.reshape(N)


def trimmed_mean_ref(gs, live, *, trim: int = 1):
    """Sort-based trimmed-mean oracle.

    Dead rows become NaN, which ``jnp.sort`` places last per coordinate,
    so live values occupy ranks [0, n_live) and the kept window is
    ranks [trim, n_live − trim)."""
    x = jnp.where(live[:, None] > 0, gs.astype(jnp.float32), jnp.nan)
    ranked = jnp.sort(x, axis=0)
    n_live = jnp.sum(live > 0)
    rank = jnp.arange(gs.shape[0])[:, None]
    keep = (rank >= trim) & (rank < n_live - trim)
    kept_sum = jnp.sum(jnp.where(keep, ranked, 0.0), axis=0)
    return kept_sum / (n_live - 2 * trim)
