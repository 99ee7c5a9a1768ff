"""Pure-jnp oracles for the grouped matrix products.

Group g owns rows [offset_g, offset_g + size_g) of the row-sorted
operand, offset_g the sum of the sizes before it; rows past the last
group give zeros. Products accumulate in float32 and return in the
operand's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes):
    """lhs (m, k), rhs (G, k, n) -> (m, n): `jax.lax.ragged_dot`."""
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                             preferred_element_type=jnp.float32)
    return out.astype(lhs.dtype)


def group_of_rows(group_sizes, m: int):
    """(m,) group of each row; G for the rows past the last group."""
    ends = jnp.cumsum(group_sizes)
    return jnp.searchsorted(ends, jnp.arange(m), side="right")


def gmm_masked_ref(lhs, rhs, group_sizes):
    """The same product as a masked einsum over every group (a second
    oracle, written without ragged_dot)."""
    G = rhs.shape[0]
    onehot = (group_of_rows(group_sizes, lhs.shape[0])[:, None]
              == jnp.arange(G)[None, :]).astype(jnp.float32)
    per_group = jnp.einsum("mk,gkn->gmn", lhs, rhs,
                           preferred_element_type=jnp.float32)
    return jnp.einsum("mg,gmn->mn", onehot, per_group).astype(lhs.dtype)


def tgmm_ref(lhs, rhs, group_sizes):
    """lhs (m, k), rhs (m, n) -> (G, k, n): each group's lhs rows,
    transposed, times its rhs rows."""
    G = group_sizes.shape[0]
    onehot = (group_of_rows(group_sizes, lhs.shape[0])[:, None]
              == jnp.arange(G)[None, :]).astype(lhs.dtype)
    out = jnp.einsum("mg,mk,mn->gkn", onehot, lhs, rhs,
                     preferred_element_type=jnp.float32)
    return out.astype(rhs.dtype)
