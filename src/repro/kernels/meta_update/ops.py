"""Dispatchers for the fused meta-step ops.

impl: "xla" (tree_map / jnp), "pallas", "pallas_interpret", selected
per call or scoped with :func:`use_impl`; unset, the platform picks
("pallas" on TPU, "xla" elsewhere — ``kernels/dispatch.py``, DESIGN.md
§5). One switch governs all three fused ops — inner update, weighted
aggregation, outer Adam — so a config flips the whole pipeline.

The pallas paths run on the packed parameter plane (``utils/flat.py``):
the flattening spec (treedef, offsets, padding) is computed once per
tree structure and memoized, so repeated calls — e.g. the inner update
inside every client of every round — never recompute the layout.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import ImplChoice
from repro.kernels.meta_update import ref
from repro.kernels.meta_update.compress import (CODECS,  # noqa: F401
                                                CompressionConfig,
                                                int8_aggregate_flat,
                                                int8_aggregate_ref,
                                                int8_encode_flat,
                                                int8_encode_ref,
                                                topk_aggregate_flat,
                                                topk_aggregate_ref)
from repro.kernels.meta_update.aggregate import (masked_mean_flat,
                                                 masked_mean_ref,
                                                 row_liveness,
                                                 screened_aggregate_flat,
                                                 screened_aggregate_ref,
                                                 trimmed_mean_flat,
                                                 trimmed_mean_ref,
                                                 weighted_aggregate_flat,
                                                 weighted_aggregate_ref)
from repro.kernels.meta_update.fused import (TILE,  # noqa: F401 (re-export)
                                             inner_update_plane,
                                             meta_update_flat)
from repro.utils.flat import plane_for

_IMPL = ImplChoice("meta_update")
resolve_impl = _IMPL.resolve
use_impl = _IMPL.use


def meta_update(theta, alpha, grads, *, impl: str | None = None):
    """θ' = θ − α ∘ g; α is a scalar or a pytree matching θ.

    The pallas paths route through the plane kernel's custom VJP
    (``inner_update``), so the tree inner loop stays reverse-
    differentiable under a pallas impl (second-order MAML/Meta-SGD used
    to hit the missing pallas_call VJP here)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.meta_update_ref(theta, alpha, grads)
    plane = plane_for(theta)
    t = plane.pack(theta)
    a = alpha if isinstance(alpha, (int, float)) else plane.pack(alpha)
    out = inner_update(t, a, plane.pack(grads), impl=impl)
    return plane.unpack_ad(out)


def inner_update(theta, alpha, g, *, impl: str | None = None):
    """Fused inner update on flat client-plane buffers, differentiable.

    theta, g: (C, N) — or (N,), treated as a one-client plane — with N a
    multiple of flat.ALIGN. alpha: python scalar, (N,) shared rates, or
    a (C, N) per-client block. "xla" is the fused-elementwise oracle;
    the pallas paths run the single-pass plane kernel
    (``fused.inner_update_plane``) with its custom VJP, so second-order
    algorithms can differentiate straight through it."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.inner_update_plane_ref(theta, alpha, g)
    if not isinstance(alpha, (int, float)) and alpha.ndim == 0:
        # a 0-d array (e.g. a traced learning rate) can't be baked into
        # the kernel as a compile-time scalar; run it as shared rates
        alpha = jnp.broadcast_to(alpha, theta.shape[-1:])
    squeeze = theta.ndim == 1
    if squeeze:
        theta, g = theta[None], g[None]
        if not isinstance(alpha, (int, float)) and alpha.ndim == 2:
            raise ValueError("2-D alpha with 1-D theta")
    out = inner_update_plane(theta, alpha, g,
                             interpret=(impl == "pallas_interpret"))
    return out[0] if squeeze else out


def weighted_aggregate(gs, w, *, impl: str | None = None):
    """(m, N) packed client grads × (m,) weights -> (N,) Σ_u w_u·g_u."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return weighted_aggregate_ref(gs, w)
    return weighted_aggregate_flat(gs, w,
                                   interpret=(impl == "pallas_interpret"))


def int8_encode(G, *, impl: str | None = None):
    """(m, N) block -> (q int8, (m,) f32 scales, (m, N) f32 residual).

    Per-row-scaled int8 quantization with the error-feedback residual
    emitted in the same pass (compress.py). "xla" runs the pure-jnp
    oracle; the pallas paths run the fused encode kernel."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return int8_encode_ref(G)
    return int8_encode_flat(G, interpret=(impl == "pallas_interpret"))


def int8_aggregate(q, scales, w, *, impl: str | None = None):
    """Dequantize-and-aggregate Σ_u w_u·s_u·q_u -> (N,) f32, fused into
    the weighted-aggregate kernel (the scale folds into the weight)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return int8_aggregate_ref(q, scales, w)
    return int8_aggregate_flat(q, scales, w,
                               interpret=(impl == "pallas_interpret"))


def topk_aggregate(vals, idx, w, n: int, *, impl: str | None = None):
    """Decode-and-aggregate (m, k) top-k uploads -> (n,) f32 weighted
    sum. (Encoding is ``compress.topk_encode`` on every impl: per-row
    selection is one XLA ``lax.top_k`` — a pallas sort network is out
    of scope, documented in compress.py.)"""
    impl = resolve_impl(impl)
    if impl == "xla":
        return topk_aggregate_ref(vals, idx, w, n)
    return topk_aggregate_flat(vals, idx, w, n,
                               interpret=(impl == "pallas_interpret"))


AGGREGATORS = ("mean", "masked_mean", "screen", "trimmed")


def robust_aggregate(gs, w, *, aggregator: str = "mean",
                     impl: str | None = None, screen_factor: float = 3.0,
                     trim: int = 1):
    """Failure-plane reduction over the (m, N) client block (§14).

      mean         Σ w·g — the plain weighted kernel, caller-normalized
                   weights; byte-for-byte today's path.
      masked_mean  Σ w·g / Σ w — renormalizes over arrived (w > 0) rows,
                   so dropouts shrink the round, not the gradient.
      screen       reject non-finite rows, clip rows with
                   ‖g‖ > screen_factor × median(live ‖g‖), renormalize.
      trimmed      coordinate-wise trimmed mean over live (arrived,
                   finite) rows, dropping the ``trim`` largest and
                   smallest values per coordinate — unweighted, the
                   classic Byzantine-robust estimator.

    All four share the impl switch; non-mean aggregators may return a
    non-finite result on degenerate rounds (every row dead, or fewer
    than 2·trim + 1 live rows) — that is deliberate: the engine's
    non-finite guard turns it into a skipped round."""
    impl = resolve_impl(impl)
    interp = impl == "pallas_interpret"
    if aggregator == "mean":
        return weighted_aggregate(gs, w, impl=impl)
    if aggregator == "masked_mean":
        if impl == "xla":
            return masked_mean_ref(gs, w)
        return masked_mean_flat(gs, w, interpret=interp)
    if aggregator == "screen":
        if impl == "xla":
            return screened_aggregate_ref(gs, w, factor=screen_factor)
        return screened_aggregate_flat(gs, w, factor=screen_factor,
                                       interpret=interp)
    if aggregator == "trimmed":
        live = row_liveness(gs, w)
        if impl == "xla":
            return trimmed_mean_ref(gs, live, trim=trim)
        return trimmed_mean_flat(gs, live, trim=trim, interpret=interp)
    raise ValueError(f"unknown aggregator {aggregator!r}; "
                     f"expected one of {AGGREGATORS}")
