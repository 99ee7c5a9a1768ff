"""Dispatcher for the grouped matrix product of the held experts.

    gmm(lhs, rhs, group_sizes)  lhs (m, k) rows sorted by group, rhs
                                (G, k, n), group_sizes (G,) int32 ->
                                (m, n); rows past the last group are 0

impl (``kernels/dispatch.py``):
  "xla"              — ``jax.lax.ragged_dot`` and its own gradient (run
                       one batch row at a time under ``vmap``, which
                       ragged_dot does not batch)
  "pallas"           — ``expert_gmm`` forward; ``expert_gmm`` (input
                       gradient) and ``expert_tgmm`` (weight gradient)
                       backward, under a custom VJP
  "pallas_interpret" — the same kernels in interpret mode (CPU tests)
Unset, the platform picks: the kernels on a TPU, ragged_dot elsewhere.

Products take their operands in their dtype (bfloat16 in the model) and
accumulate in float32. Shapes are static and the group sizes are data,
so a step compiles once whatever the routing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import ImplChoice
from repro.kernels.grouped_matmul import gmm as kernel
from repro.kernels.grouped_matmul import ref

_IMPL = ImplChoice("grouped_matmul")
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use

# Tiles: rows per tile; the largest multiple of 128 up to the preference
# that divides a width, or the whole width up to FULL_WIDTH (1408, the
# expert width, is 11 x 128). Chosen by the chip sweep in PERF.md §6: at
# deepseek-v2-lite's widths expert_gmm takes whole (2048 x 1408)
# weight blocks, which stay in VMEM across a group's row tiles.
TILE_ROWS = 128
FULL_WIDTH = 1536
GMM_PREF = (2048, 2048)      # (contracted, output) widths of expert_gmm
TGMM_PREF = (1024, 1024)     # (k, n) of expert_tgmm's (G, k, n) output


def tile(width: int, pref: int) -> int:
    if width <= FULL_WIDTH:
        return width
    fits = [t for t in range(128, pref + 1, 128) if width % t == 0]
    return fits[-1] if fits else width


def _pad_rows(x, m_pad: int):
    m = x.shape[0]
    return x if m_pad == m else jnp.pad(x, ((0, m_pad - m), (0, 0)))


def _padded(m: int) -> int:
    return -(-m // TILE_ROWS) * TILE_ROWS


def _run_gmm(lhs, rhs, group_sizes, interpret: bool, transpose_rhs: bool):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = (TILE_ROWS, tile(k, GMM_PREF[0]), tile(n, GMM_PREF[1]))
    out = kernel.gmm(_pad_rows(lhs, _padded(m)), rhs, group_sizes,
                     tiling=tiling, transpose_rhs=transpose_rhs,
                     interpret=interpret)[:m]
    # rows past the last group are never written: give them zeros
    rows = jnp.arange(m) < jnp.sum(group_sizes)
    return jnp.where(rows[:, None], out, jnp.zeros((), out.dtype))


def _run_tgmm(lhs, rhs, group_sizes, interpret: bool):
    m, k = lhs.shape
    n = rhs.shape[1]
    tiling = (TILE_ROWS, tile(k, TGMM_PREF[0]), tile(n, TGMM_PREF[1]))
    m_pad = _padded(m)
    return kernel.tgmm(_pad_rows(lhs, m_pad), _pad_rows(rhs, m_pad),
                       group_sizes, tiling=tiling, interpret=interpret)


_ragged = jax.custom_batching.sequential_vmap(ref.gmm_ref)
_ragged_vjp = jax.custom_batching.sequential_vmap(
    lambda lhs, rhs, group_sizes, g: jax.vjp(
        lambda a, b: ref.gmm_ref(a, b, group_sizes), lhs, rhs)[1](g))


@jax.custom_vjp
def _gmm_xla(lhs, rhs, group_sizes):
    return _ragged(lhs, rhs, group_sizes)


def _gmm_xla_fwd(lhs, rhs, group_sizes):
    return _ragged(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_xla_bwd(res, g):
    d_lhs, d_rhs = _ragged_vjp(*res, g)
    return d_lhs, d_rhs, None


_gmm_xla.defvjp(_gmm_xla_fwd, _gmm_xla_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_kernels(lhs, rhs, group_sizes, interpret):
    return _run_gmm(lhs, rhs, group_sizes, interpret, False)


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return (_run_gmm(lhs, rhs, group_sizes, interpret, False),
            (lhs, rhs, group_sizes))


def _gmm_bwd(interpret, res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = _run_gmm(g, rhs, group_sizes, interpret, True)
    d_rhs = _run_tgmm(lhs, g, group_sizes, interpret).astype(rhs.dtype)
    return d_lhs, d_rhs, None


_gmm_kernels.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs, rhs, group_sizes, *, impl: str | None = None):
    """lhs (m, k), rhs (G, k, n), group_sizes (G,) int32 -> (m, n) in
    lhs's dtype; differentiable in lhs and rhs."""
    impl = resolve_impl(impl)
    group_sizes = group_sizes.astype(jnp.int32)
    if impl == "xla":
        return _gmm_xla(lhs, rhs, group_sizes)
    return _gmm_kernels(lhs, rhs, group_sizes, impl == "pallas_interpret")
