"""Pallas TPU kernels for the fused inner update (single θ and client
plane).

The inner update θ' = θ − α ∘ g is executed once per client per inner
step per round over the full parameter vector — pure memory traffic
(3 reads, 1 write, 1 FMA per element). Unfused, XLA emits it per-leaf as
mul+sub pairs; the kernels stream 128-lane-aligned tiles through VMEM in
a single pass, which is the roofline-optimal schedule for this op on
TPU.

Two layouts, both over the packed parameter plane (`utils/flat.py`):

- ``meta_update_flat``: one client, flat (N,) buffers (N a multiple of
  ALIGN = 8 * 128), 1-D grid over (block_rows, 128) tiles — the
  deployment/adapt path.
- ``inner_update_plane``: a chunk of C clients adapting in lockstep on a
  (C, N) client plane, read in its own 2-D layout: lane-dense
  ``(c_blk, cols)`` blocks from ``plane_block`` over a ``pl.cdiv`` grid
  (no (C, N/128, 128) view, which XLA would relayout, and no divisor
  search, which degenerates to 8-row tiles when N/1024 is prime), with
  θ aliased to the output so the plane updates in place across inner
  steps. α is a compile-time scalar (MAML/FOMAML/Reptile), a shared
  (N,) vector, or a per-client (C, N) block (Meta-SGD, where α rides the
  plane as a learnable input). ``inner_update_plane`` carries a custom VJP
  (θ' = θ − α∘g ⇒ dθ = ḡ, dα = −g∘ḡ reduced to α's shape, dg = −α∘ḡ) so
  MAML/Meta-SGD can reverse-differentiate through the fused kernel; the
  backward is plain jnp — elementwise, fused by XLA, and only live on
  second-order paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
SUBLANE = 8
MAX_BLOCK_ROWS = 8 * 64      # 64k elements per grid step (~256 KiB f32)
TILE = MAX_BLOCK_ROWS * LANE  # kept for back-compat with older callers
# VMEM for the blocks of a (C, N) plane kernel, all streams double-
# buffered: inside the 16 MiB of scoped VMEM a TPU v5e kernel gets by
# default
PLANE_VMEM_BYTES = 12 << 20


def choose_block_rows(total_rows: int, max_rows: int = MAX_BLOCK_ROWS) -> int:
    """Largest divisor of ``total_rows`` that is ≤ max_rows and a multiple
    of SUBLANE (total_rows is guaranteed sublane-aligned by flat.ALIGN).

    For the 1-D kernels' (total_rows, 128) views; when total_rows / 8 is
    prime this falls to 8-row tiles. (C, N) plane kernels use
    ``plane_block`` instead."""
    assert total_rows % SUBLANE == 0, total_rows
    k = total_rows // SUBLANE
    cap = max(1, max_rows // SUBLANE)
    d = max(x for x in range(1, min(cap, k) + 1) if k % x == 0)
    return SUBLANE * d


def _meta_update_kernel(theta_ref, alpha_ref, g_ref, out_ref):
    out_ref[...] = (theta_ref[...].astype(jnp.float32)
                    - alpha_ref[...].astype(jnp.float32)
                    * g_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def meta_update_flat(theta, alpha, g, *, interpret: bool = False):
    """theta, alpha, g: flat (N,) with N % (8*128) == 0. Returns θ − α∘g."""
    (N,) = theta.shape
    assert N % (SUBLANE * LANE) == 0, N
    total_rows = N // LANE
    rows = choose_block_rows(total_rows)
    n_tiles = total_rows // rows

    def reshape(x):
        return x.reshape(total_rows, LANE)

    spec = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        _meta_update_kernel,
        grid=(n_tiles,),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((total_rows, LANE), theta.dtype),
        interpret=interpret,
    )(reshape(theta), reshape(alpha), reshape(g))
    return out.reshape(N)


# ---- client-plane inner update ------------------------------------------

def _plane_kernel_scalar(theta_ref, g_ref, out_ref, *, alpha):
    out_ref[...] = (theta_ref[...].astype(jnp.float32)
                    - alpha * g_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _plane_kernel_vec(theta_ref, alpha_ref, g_ref, out_ref):
    out_ref[...] = (theta_ref[...].astype(jnp.float32)
                    - alpha_ref[...].astype(jnp.float32)
                    * g_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def sublane_rows(itemsize: int) -> int:
    """Rows of one (rows, 128) VMEM tile: 8 of f32, 16 of bf16, 32 of
    int8."""
    return SUBLANE * max(1, 4 // itemsize)


def plane_block(C: int, N: int, itemsize: int, streams: int):
    """Lane-dense ``(c_blk, cols)`` block of a (C, N) plane read in its
    own 2-D layout, for a kernel that moves ``streams`` such blocks (its
    inputs and outputs) each grid step.

    The blocks of all streams, double-buffered, share PLANE_VMEM_BYTES.
    ``c_blk`` is all of C unless a 128-column strip of every client
    overflows a stream's share; then it is the largest multiple of the
    dtype's sublane tile that leaves 128 columns. ``cols`` fills the
    rest, a multiple of 128 and no wider than the plane. Callers run a
    ``pl.cdiv`` grid: a ragged last block is masked, which is safe
    because every plane kernel is independent per column."""
    sub = sublane_rows(itemsize)
    per_block = PLANE_VMEM_BYTES // (2 * streams)
    c_blk = C
    if _round_up(C, sub) * LANE * itemsize > per_block:
        c_blk = max(sub, per_block // (LANE * itemsize) // sub * sub)
    cols = per_block // (_round_up(c_blk, sub) * itemsize) // LANE * LANE
    return c_blk, max(LANE, min(cols, _round_up(N, LANE)))


# NOTE: deliberately NOT wrapped in jax.jit. Production callers jit the
# whole meta step, so compiled-mode dispatch cost is irrelevant, and the
# custom call then takes the enclosing jitted function's name (`step` in
# the packed meta step), by which the benchmark's meta_update_roofline
# finds the inner update in a trace. In interpret mode the kernel runs
# as one XLA:CPU program, which contracts θ − α∘g into an FMA: a
# bit-exact oracle is the jnp expression compiled as one program too.
def _inner_plane_scalar_call(theta, g, *, alpha: float,
                             interpret: bool = False):
    C, N = theta.shape
    c_blk, cols = plane_block(C, N, theta.dtype.itemsize, streams=3)
    spec = pl.BlockSpec((c_blk, cols), lambda c, j: (c, j))
    return pl.pallas_call(
        functools.partial(_plane_kernel_scalar, alpha=alpha),
        grid=(pl.cdiv(C, c_blk), pl.cdiv(N, cols)),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((C, N), theta.dtype),
        input_output_aliases={0: 0},      # θ plane updates in place
        interpret=interpret,
    )(theta, g)


def _inner_plane_vec_call(theta, alpha, g, *, interpret: bool = False):
    # un-jitted on purpose — see _inner_plane_scalar_call
    C, N = theta.shape
    c_blk, cols = plane_block(C, N, theta.dtype.itemsize, streams=4)
    spec = pl.BlockSpec((c_blk, cols), lambda c, j: (c, j))
    if alpha.ndim == 1:        # shared (N,) α, broadcast over the chunk
        a_spec = pl.BlockSpec((1, cols), lambda c, j: (0, j))
        alpha = alpha.reshape(1, N)
    else:                      # per-client (C, N) α block
        a_spec = spec
    return pl.pallas_call(
        _plane_kernel_vec,
        grid=(pl.cdiv(C, c_blk), pl.cdiv(N, cols)),
        in_specs=[spec, a_spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((C, N), theta.dtype),
        input_output_aliases={0: 0},
        interpret=interpret,
    )(theta, alpha, g)


def _reduce_to_shape(x, shape):
    """Sum-reduce ``x`` down to ``shape`` (inverse of broadcasting)."""
    if x.shape == tuple(shape):
        return x
    extra = x.ndim - len(shape)
    if extra:
        x = jnp.sum(x, axis=tuple(range(extra)))
    axes = tuple(i for i, d in enumerate(shape) if d == 1 and x.shape[i] != 1)
    if axes:
        x = jnp.sum(x, axis=axes, keepdims=True)
    return x


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _inner_plane_scalar(alpha, interpret, theta, g):
    return _inner_plane_scalar_call(theta, g, alpha=alpha,
                                    interpret=interpret)


def _inner_plane_scalar_fwd(alpha, interpret, theta, g):
    return _inner_plane_scalar(alpha, interpret, theta, g), None


def _inner_plane_scalar_bwd(alpha, interpret, _res, ct):
    return ct, -alpha * ct


_inner_plane_scalar.defvjp(_inner_plane_scalar_fwd, _inner_plane_scalar_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _inner_plane_vec(interpret, theta, alpha, g):
    return _inner_plane_vec_call(theta, alpha, g, interpret=interpret)


def _inner_plane_vec_fwd(interpret, theta, alpha, g):
    return _inner_plane_vec(interpret, theta, alpha, g), (alpha, g)


def _inner_plane_vec_bwd(interpret, res, ct):
    alpha, g = res
    d_alpha = _reduce_to_shape(-g * ct, alpha.shape)
    return ct, d_alpha, -alpha * ct


_inner_plane_vec.defvjp(_inner_plane_vec_fwd, _inner_plane_vec_bwd)


def inner_update_plane(theta, alpha, g, *, interpret: bool = False):
    """Fused θ ← θ − α∘g over a (C, N) client plane, differentiable.

    theta, g: (C, N). alpha: python scalar
    (compile-time constant baked into the kernel), (N,) shared
    per-coordinate rates, or (C, N) per-client rates. Input/output
    aliasing updates θ in place; a custom VJP makes the op safe under
    reverse-mode autodiff (second-order MAML / Meta-SGD)."""
    if isinstance(alpha, (int, float)):
        return _inner_plane_scalar(float(alpha), interpret, theta, g)
    return _inner_plane_vec(interpret, theta, alpha, g)
