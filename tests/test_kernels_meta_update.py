"""Fused meta-update kernel vs oracle, incl. hypothesis property sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# `propsweep` re-exports hypothesis when installed, else a
# deterministic seeded sweep — no skip either way.
from propsweep import given, settings, st

from repro.kernels.meta_update import fused
from repro.kernels.meta_update.aggregate import (weighted_aggregate_flat,
                                                 weighted_aggregate_ref)
from repro.kernels.meta_update.fused import LANE, SUBLANE
from repro.kernels.meta_update.ops import meta_update
from repro.kernels.meta_update.ref import inner_update_plane_ref


def _tree(rng, shapes, dtype=jnp.float32):
    return {f"p{i}": jnp.asarray(rng.normal(0, 1, s), dtype)
            for i, s in enumerate(shapes)}


@pytest.mark.parametrize("shapes", [
    [(7,)], [(128, 128)], [(3, 5, 7), (2,), (1000,)],
])
@pytest.mark.parametrize("scalar_alpha", [True, False])
def test_fused_matches_ref(rng, shapes, scalar_alpha):
    theta = _tree(rng, shapes)
    g = _tree(rng, shapes)
    alpha = 0.01 if scalar_alpha else jax.tree.map(
        lambda x: jnp.abs(x) * 0.01, _tree(rng, shapes))
    ref = meta_update(theta, alpha, g, impl="xla")
    out = meta_update(theta, alpha, g, impl="pallas_interpret")
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-6, atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 4000), lr=st.floats(1e-5, 1.0),
       seed=st.integers(0, 2**31 - 1))
def test_property_inner_update(n, lr, seed):
    """θ' = θ − lr·g exactly, for arbitrary sizes/lrs (property test)."""
    r = np.random.RandomState(seed)
    theta = {"w": jnp.asarray(r.normal(0, 1, (n,)), jnp.float32)}
    g = {"w": jnp.asarray(r.normal(0, 1, (n,)), jnp.float32)}
    out = meta_update(theta, lr, g, impl="pallas_interpret")
    expect = np.asarray(theta["w"]) - lr * np.asarray(g["w"])
    np.testing.assert_allclose(np.asarray(out["w"]), expect,
                               rtol=1e-5, atol=1e-5)


# ---- client-plane kernels: lane-dense (c_blk, cols) tiles ---------------

# N/1024 prime (13, 61): the old largest-divisor row count fell to 8-row
# tiles on such planes
PLANE_N = [13 * 1024, 61 * 1024]
PLANE_C = [1, 3, 8, 32]
RAGGED_COLS = 3 * LANE          # divides neither plane width


def _budget_for_cols(monkeypatch, C, streams, tile):
    """``tile="ragged"`` shrinks the VMEM budget so the helper picks
    RAGGED_COLS columns and the last block of the grid is ragged."""
    if tile == "ragged":
        rows = -(-C // SUBLANE) * SUBLANE
        monkeypatch.setattr(fused, "PLANE_VMEM_BYTES",
                            RAGGED_COLS * rows * 4 * 2 * streams)


@pytest.mark.parametrize("tile", ["default", "ragged"])
@pytest.mark.parametrize("alpha_kind", ["scalar", "shared", "per_client"])
@pytest.mark.parametrize("C", PLANE_C)
@pytest.mark.parametrize("N", PLANE_N)
def test_inner_update_plane_tiles(monkeypatch, rng, N, C, alpha_kind, tile):
    """θ − α∘g bit-identical to the oracle, θ updated in place under
    donation. Both sides run as one compiled program, so XLA:CPU rounds
    them alike (it contracts θ − α∘g into an FMA in either)."""
    streams = 3 if alpha_kind == "scalar" else 4
    _budget_for_cols(monkeypatch, C, streams, tile)
    c_blk, cols = fused.plane_block(C, N, 4, streams)
    if tile == "ragged":
        assert (c_blk, cols) == (C, RAGGED_COLS) and N % cols
    T = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    G = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    if alpha_kind == "scalar":
        alpha = 0.05
        expect = jax.jit(lambda t, g: inner_update_plane_ref(t, alpha, g))(T, G)
        f = jax.jit(lambda t, g: fused.inner_update_plane(
            t, alpha, g, interpret=True), donate_argnums=0)
        args = (G,)
    else:
        shape = (N,) if alpha_kind == "shared" else (C, N)
        alpha = jnp.asarray(rng.uniform(0, 0.1, shape), jnp.float32)
        expect = jax.jit(inner_update_plane_ref)(T, alpha, G)
        f = jax.jit(lambda t, a, g: fused.inner_update_plane(
            t, a, g, interpret=True), donate_argnums=0)
        args = (alpha, G)
    ptr = T.unsafe_buffer_pointer()
    out = f(T, *args)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    assert T.is_deleted() and out.unsafe_buffer_pointer() == ptr


@pytest.mark.parametrize("tile", ["default", "ragged"])
@pytest.mark.parametrize("C", PLANE_C + [33])
@pytest.mark.parametrize("N", PLANE_N)
def test_weighted_aggregate_tiles(monkeypatch, rng, N, C, tile):
    """Σ_u w_u·g_u over (C, cols) blocks of the plane as stored; 33
    clients leave a partial tile of clients after four whole ones."""
    _budget_for_cols(monkeypatch, C, 2, tile)
    gs = jnp.asarray(rng.normal(0, 1, (C, N)), jnp.float32)
    w = jnp.asarray(rng.uniform(0, 1, (C,)), jnp.float32)
    out = weighted_aggregate_flat.__wrapped__(gs, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(weighted_aggregate_ref(gs, w)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("streams", [2, 3, 4])
def test_plane_block(streams):
    """The femnist plane (32 × 6,603,776 f32) runs a few hundred grid
    steps of blocks inside the VMEM budget, and planes whose N/1024 is
    prime keep whole-plane-height, wide blocks."""
    C, N = 32, 6603776
    c_blk, cols = fused.plane_block(C, N, 4, streams)
    assert c_blk == C and cols % LANE == 0
    assert -(-N // cols) <= 1024
    assert 2 * streams * c_blk * cols * 4 <= fused.PLANE_VMEM_BYTES
    for n in [13 * 1024, 61 * 1024, 6449 * 1024]:
        for c in PLANE_C:
            c_blk, cols = fused.plane_block(c, n, 4, streams)
            assert c_blk == c and cols >= min(n, 4096), (c, n, cols)
    # more clients than a 128-column strip holds: split into row tiles
    c_blk, cols = fused.plane_block(100_000, N, 4, streams)
    assert c_blk % SUBLANE == 0 and cols == LANE
