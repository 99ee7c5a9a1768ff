"""Grouped matrix products over ragged row groups (Pallas, TPU).

The rows of `lhs` are sorted by group: group g owns rows
[offset_g, offset_g + size_g), offset_g = size_0 + ... + size_{g-1}.
The sizes change from call to call and the shapes do not, so one
executable serves every routing. Rows past the last group are not
computed.

  expert_gmm   out[rows of g] = lhs[rows of g] @ rhs[g]       (m, n)
               (rhs[g]^T with transpose_rhs: the input gradient)
  expert_tgmm  out[g] = lhs[rows of g]^T @ rhs[rows of g]     (G, k, n)
               (the weight gradient; an empty group gives zeros)

Both walk a grid over (n tiles, row tiles, k tiles) in which the row
tiles are the (tile, group) pairs the sizes make: a row tile that two
groups share is visited once by each, with the rows of the other group
masked. The number of such visits is computed on the device and sets
the grid's extent, so only tiles that hold rows are visited. Operands
enter the MXU in their own dtype (bfloat16) and accumulate in a float32
scratch. The visit schedule is megablox's (`jax.experimental.pallas.ops
.tpu.megablox`, `make_group_metadata`); the kernels are written here so
that they carry the names the device trace reports them by.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

GMM_NAME = "expert_gmm"
TGMM_NAME = "expert_tgmm"


def _rows_mask(metadata, visit, tm: int, cols: int):
    """(tm, cols) mask of the rows of this visit's row tile that belong
    to this visit's group."""
    offsets, group_ids, tile_ids = metadata
    g = group_ids[visit]
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, cols), 0) \
        + tile_ids[visit] * tm
    return (row >= offsets[g]) & (row < offsets[g + 1])


def _metadata(group_sizes, m: int, tm: int, visit_empty: bool):
    return make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32),
        num_nonzero_groups=group_sizes.shape[0],
        visit_empty_groups=visit_empty)


@functools.partial(jax.jit, static_argnames=("tiling", "transpose_rhs",
                                             "interpret"))
def gmm(lhs, rhs, group_sizes, *, tiling: tuple[int, int, int],
        transpose_rhs: bool = False, interpret: bool = False):
    """lhs (m, k), rhs (G, k, n) — (G, n, k) with transpose_rhs —,
    group_sizes (G,) int32 -> (m, n) in lhs's dtype. `tiling` (tm, tk,
    tn) divides (m, k, n). Rows past the groups are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (m, k, n, tiling)
    metadata, visits = _metadata(group_sizes, m, tm, visit_empty=False)

    def kernel(metadata, lhs_ref, rhs_ref, out_ref, acc_ref):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
            (((1,), (0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == pl.num_programs(2) - 1)
        def _store():
            mask = _rows_mask(metadata, visit, tm, tn)
            out_ref[...] = jax.lax.select(
                mask, acc_ref[...],
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

    def lhs_map(n_i, visit, k_i, metadata):
        return metadata[2][visit], k_i

    def rhs_map(n_i, visit, k_i, metadata):
        if transpose_rhs:
            return metadata[1][visit], n_i, k_i
        return metadata[1][visit], k_i, n_i

    def out_map(n_i, visit, k_i, metadata):
        return metadata[2][visit], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, visits, k // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=GMM_NAME,
    )(metadata, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tiling", "interpret"))
def tgmm(lhs, rhs, group_sizes, *, tiling: tuple[int, int, int],
         interpret: bool = False):
    """lhs (m, k), rhs (m, n), group_sizes (G,) int32 -> (G, k, n) in
    rhs's dtype: each group's lhs rows, transposed, times its rhs rows.
    `tiling` (tm, tk, tn) divides (m, k, n)."""
    m, k = lhs.shape
    n = rhs.shape[1]
    G = group_sizes.shape[0]
    tm, tk, tn = tiling
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (m, k, n, tiling)
    metadata, visits = _metadata(group_sizes, m, tm, visit_empty=True)

    def kernel(metadata, lhs_ref, rhs_ref, out_ref, acc_ref):
        visit = pl.program_id(2)
        offsets, group_ids, _ = metadata
        g = group_ids[visit]
        prev = group_ids[jnp.maximum(visit - 1, 0)]
        last = visit == pl.num_programs(2) - 1
        nxt = group_ids[jnp.where(last, visit, visit + 1)]

        @pl.when((visit == 0) | (prev != g))
        def _zero():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[g + 1] > offsets[g])
        def _accumulate():
            # rows of other groups are zeroed (as float32: v5e has no
            # bfloat16 select), then the operands return to their dtype
            x = jax.lax.select(_rows_mask(metadata, visit, tm, tk),
                               lhs_ref[...].astype(jnp.float32),
                               jnp.zeros((tm, tk), jnp.float32))
            y = jax.lax.select(_rows_mask(metadata, visit, tm, tn),
                               rhs_ref[...].astype(jnp.float32),
                               jnp.zeros((tm, tn), jnp.float32))
            acc_ref[...] += jax.lax.dot(
                x.swapaxes(0, 1).astype(lhs_ref.dtype),
                y.astype(rhs_ref.dtype),
                preferred_element_type=jnp.float32)

        @pl.when(last | (nxt != g))
        def _store():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, visit, metadata):
        return metadata[2][visit], k_i

    def rhs_map(n_i, k_i, visit, metadata):
        return metadata[2][visit], n_i

    def out_map(n_i, k_i, visit, metadata):
        return metadata[1][visit], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((G, k, n), rhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=TGMM_NAME,
    )(metadata, lhs, rhs)
