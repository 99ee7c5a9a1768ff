"""The packed parameter plane: φ as one padded, lane-aligned flat buffer.

Every per-round server op (client-gradient aggregation, outer Adam, the
fused inner update) is pure memory traffic over the full parameter set.
Executing those ops per-leaf costs one XLA op pair per tensor and forces
re-flattening on every call; the plane instead computes the layout
*once* — treedef, per-leaf offsets, padded size — and keeps the whole
meta-step on a single ``(n_padded,)`` float32 buffer (see DESIGN.md §2
for the layout and dtype policy).

Alignment: ``n_padded`` is a multiple of ``ALIGN = 8 * 128`` elements so
any slice of the plane reshapes to whole (sublane, lane) = (8, 128) TPU
tiles, which is what the Pallas kernels in ``kernels/meta_update`` and
``optim/fused_adam`` require.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

ALIGN = 8 * 128          # one (sublane, lane) f32 tile


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside the plane."""
    offset: int
    size: int
    shape: tuple
    dtype: str


@dataclasses.dataclass(frozen=True)
class FlatPlane:
    """Cached flattening spec for one pytree structure.

    Hashable and shape-only, so it can be closed over by jitted
    functions without retriggering tracing; ``pack``/``unpack`` are the
    only data-touching methods.
    """
    treedef: Any
    slots: tuple          # tuple[LeafSlot, ...] in treedef leaf order
    n_real: int
    n_padded: int

    @classmethod
    def from_tree(cls, tree, align: int = ALIGN) -> "FlatPlane":
        leaves, treedef = jax.tree.flatten(tree)
        slots, off = [], 0
        for x in leaves:
            size = int(np.prod(x.shape)) if x.shape else 1
            slots.append(LeafSlot(off, size, tuple(x.shape),
                                  jnp.dtype(x.dtype).name))
            off += size
        n_padded = off + ((-off) % align)
        return cls(treedef, tuple(slots), off, max(n_padded, align))

    # ---- data movement --------------------------------------------------
    def pack(self, tree):
        """tree -> (n_padded,) float32 plane (zero pad tail).

        The plane packs via a dynamic-update-slice chain into a zeroed
        plane rather than an L-way concatenate: XLA:CPU executes the DUS
        chain in place (faster than its many-operand concat), the zero
        tail comes for free, and the transpose of a DUS is a slice,
        which keeps ``pack`` cheap under autodiff."""
        leaves = jax.tree.leaves(tree)
        assert len(leaves) == len(self.slots), \
            f"tree has {len(leaves)} leaves, plane expects {len(self.slots)}"
        flat = jnp.zeros((self.n_padded,), jnp.float32)
        for s, x in zip(self.slots, leaves):
            # a short leaf would silently leave stale zeros in the
            # slot (DUS, unlike concat, cannot fail on total length)
            assert x.size == s.size, (x.shape, s)
            flat = jax.lax.dynamic_update_slice(
                flat, x.reshape(-1).astype(jnp.float32), (s.offset,))
        return flat

    def unpack(self, flat):
        """(n_padded,) plane -> tree with original shapes/dtypes."""
        out = [flat[s.offset:s.offset + s.size].reshape(s.shape)
               .astype(s.dtype) for s in self.slots]
        return jax.tree.unflatten(self.treedef, out)

    def unpack_ad(self, flat):
        """``unpack`` with an efficient reverse-mode rule.

        The built-in transpose of an unpack turns every leaf slice into
        a zero-padded full-plane buffer and sums all of them — L live
        (N,)-sized intermediates per backward pass, which is what makes
        naive grad-through-unpack explode inside the client inner loop.
        The slices are disjoint and cover the real region, so the true
        cotangent is just the concatenation of the leaf cotangents plus
        the zero alignment tail: one pass, no per-leaf planes. Use this
        form wherever the unpack sits under autodiff (the flat client
        loss); plain ``unpack`` is fine outside differentiation.
        Second-order (reverse-over-reverse) composes, because the first
        vjp resolves the custom rule into plain concat/slice ops."""
        return _unpack_ad(self, flat)

    def zeros(self):
        return jnp.zeros((self.n_padded,), jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _unpack_ad(plane, flat):
    return plane.unpack(flat)


def _unpack_ad_fwd(plane, flat):
    return plane.unpack(flat), None


def _unpack_ad_bwd(plane, _res, ct):
    # DUS chain for the same reason as pack: in-place on CPU, and its
    # own transpose (slice) stays cheap under second-order autodiff
    leaves = jax.tree.leaves(ct)
    flat_ct = jnp.zeros((plane.n_padded,), jnp.float32)
    for s, x in zip(plane.slots, leaves):
        flat_ct = jax.lax.dynamic_update_slice(
            flat_ct, x.reshape(-1).astype(jnp.float32), (s.offset,))
    return (flat_ct,)


_unpack_ad.defvjp(_unpack_ad_fwd, _unpack_ad_bwd)


# ---- spec cache ---------------------------------------------------------
_PLANE_CACHE: dict = {}


def plane_for(tree, align: int = ALIGN) -> FlatPlane:
    """FlatPlane for ``tree``'s structure, memoized by (treedef, shapes,
    dtypes) so hot paths never recompute offsets."""
    key = (jax.tree.structure(tree),
           tuple((tuple(x.shape), jnp.dtype(x.dtype).name)
                 for x in jax.tree.leaves(tree)), align)
    plane = _PLANE_CACHE.get(key)
    if plane is None:
        plane = _PLANE_CACHE[key] = FlatPlane.from_tree(tree, align)
    return plane
