"""The FedMeta server round (paper Algorithm 1, AlgorithmUpdate).

One meta-training round:
  1. a batch of m sampled clients' (support, query) data arrives with a
     leading client axis on every leaf,
  2. every client computes g_u = ModelTraining(φ; D_S^u, D_Q^u),
  3. the server updates φ with the (weighted) average of the g_u via the
     outer optimizer (Adam here, per paper A.2).

Four client execution strategies (memory/throughput tradeoff in
DESIGN.md §4):
  - "vmap": all clients in parallel (paper's `for u in parallel`; right
    choice for small models / CPU simulation),
  - "scan": clients sequential with a meta-gradient accumulator carry —
    memory-optimal (one adapted θ_u lives at a time),
  - "chunked": scan over chunks of vmapped clients — peak memory scales
    with the chunk size, not clients-per-round, while keeping vmap
    throughput inside each chunk. m need not divide the chunk size;
    the tail chunk is padded with zero-weight duplicate clients.
  - "sharded": clients split across the devices of a mesh (shard_map);
    each device reduces its local clients' gradients to a partial
    meta-gradient which is psum-reduced into the aggregate — the client
    half of the round scales with the mesh, and only (N,)-sized partials
    cross the interconnect (DESIGN.md §10).

Two parameter representations:
  - tree (default): φ stays a pytree; aggregation and the outer step run
    per-leaf,
  - packed plane (``make_packed_meta_train_step``): φ lives in one flat
    128-lane-aligned f32 buffer (utils/flat.py). Chunks of clients adapt
    in lockstep on a (C, N) client plane with the fused inner-update
    kernel, their meta-gradients come out as rows of an (m, N) block,
    the fused aggregation kernel reduces the block, and the fused
    outer-Adam kernel advances φ: the whole round is flat except the
    model forward/backward itself (DESIGN.md §9).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.meta_update import ops as mu_ops
from repro.sharding.context import get_mesh, make_mesh
from repro.utils.flat import FlatPlane, plane_for
from repro.utils.pytree import tree_add, tree_scale, tree_zeros_like


def _normalize_weights(weights, m):
    if weights is None:
        return jnp.full((m,), 1.0 / m, jnp.float32)
    weights = weights.astype(jnp.float32)
    return weights / jnp.sum(weights)


def _pad_client_axis(support, query, w, m, multiple):
    """Pad the leading client axis to a multiple of ``multiple`` with
    zero-weight copies of client 0 (w is already normalized, so the
    padding contributes exactly nothing to gradients or metrics)."""
    pad = (-m) % multiple
    if pad:
        idx = jnp.concatenate(
            [jnp.arange(m), jnp.zeros((pad,), jnp.int32)])
        support, query = jax.tree.map(lambda x: x[idx], (support, query))
        w = jnp.concatenate([w, jnp.zeros((pad,), w.dtype)])
    return support, query, w, m + pad


def _chunk_client_axis(support, query, w, m, chunk):
    """Reshape the leading client axis m -> (n_chunks, chunk), padding the
    tail with zero-weight copies of client 0 when chunk ∤ m."""
    support, query, w, m_pad = _pad_client_axis(support, query, w, m, chunk)
    n_chunks = m_pad // chunk

    def split(x):
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    support, query = jax.tree.map(split, (support, query))
    return support, query, w.reshape(n_chunks, chunk)


def _resolve_mesh(mesh, mesh_axis):
    """The mesh + axis name clients shard over.

    Precedence: explicit ``mesh=`` > the ambient mesh
    (sharding/context.py, set by the launcher) > a 1-axis "clients"
    mesh over every visible device — so ``client_axis="sharded"`` works
    out of the box on a plain host while launchers keep full control of
    device placement."""
    mesh = mesh or get_mesh()
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), ("clients",))
    return mesh, (mesh_axis or mesh.axis_names[0])


def _weighted_metrics(w, mets):
    """Per-client metrics (leading m axis) -> weighted scalar summary.

    Identical reduction on every client axis, so vmap/scan/chunked report
    the same numbers (the scan path previously took an unweighted mean)."""
    return jax.tree.map(lambda x: jnp.sum(w * x), mets)


def _scan_chunks(chunk_fn, acc0, add, support, query, w, m, chunk):
    """Scan-of-chunks reduction shared by the "chunked" axis and the
    per-device execution of the "sharded" axis.

    chunk_fn(s, q, wc) -> (partial aggregate, per-chunk weighted
    metrics); ``add`` combines partials into the ``acc0``-shaped carry.
    Returns (aggregate, metric sums)."""
    sup_c, qry_c, w_c = _chunk_client_axis(support, query, w, m, chunk)

    def body(acc, inp):
        partial, mets = chunk_fn(*inp)
        return add(acc, partial), mets

    acc, msums = jax.lax.scan(body, acc0, (sup_c, qry_c, w_c))
    return acc, jax.tree.map(jnp.sum, msums)


def _sharded_reduce(chunk_fn, acc0, add, support, query, w, m, client_chunk,
                    mesh, mesh_axis):
    """shard_map reduction shared by the tree and packed pipelines.

    Clients are padded to a device multiple and split over the mesh
    axis; each device runs chunk_fn on its local clients (scan of
    chunks when client_chunk is set) and the partial aggregates and
    weighted metrics are psum-reduced to replicated outputs. chunk_fn's
    aggregate may be a flat array or a pytree — psum maps over leaves."""
    msh, ax = _resolve_mesh(mesh, mesh_axis)
    sup_p, qry_p, w_p, m_pad = _pad_client_axis(
        support, query, w, m, msh.shape[ax])
    m_loc = m_pad // msh.shape[ax]

    def local_fn(s, q, wl):
        if client_chunk and client_chunk < m_loc:
            partial, pm = _scan_chunks(
                chunk_fn, acc0, add, s, q, wl, m_loc, client_chunk)
        else:
            partial, pm = chunk_fn(s, q, wl)
        psum = lambda t: jax.tree.map(      # noqa: E731
            lambda x: jax.lax.psum(x, ax), t)
        return psum(partial), psum(pm)

    return jax.shard_map(
        local_fn, mesh=msh, in_specs=(P(ax), P(ax), P(ax)),
        out_specs=(P(), P()), check_vma=False)(sup_p, qry_p, w_p)


def federated_meta_step(algo, optimizer, phi, opt_state, support, query,
                        weights=None, *, client_axis: str = "vmap",
                        client_chunk: int | None = None, mesh=None,
                        mesh_axis: str | None = None):
    """support/query: pytrees with leading client axis m on each leaf.
    weights: (m,) aggregation weights (paper A.2 weights by local data
    count); None = uniform 1/m. Returns (phi, opt_state, metrics).
    mesh/mesh_axis: only for client_axis="sharded" (default: the ambient
    mesh from sharding.context, its first axis)."""
    m = jax.tree.leaves(support)[0].shape[0]
    w = _normalize_weights(weights, m)

    def tree_chunk(s, q, wc):
        """Weighted per-leaf partial + weighted metrics for one chunk."""
        gs, mets = jax.vmap(
            lambda s_, q_: algo.client_grad(phi, s_, q_))(s, q)
        partial = jax.tree.map(
            lambda g: jnp.tensordot(wc, g.astype(jnp.float32), axes=1), gs)
        return partial, _weighted_metrics(wc, mets)

    def tree_acc0():
        return tree_zeros_like(
            jax.tree.map(lambda x: x.astype(jnp.float32), phi))

    if client_axis == "vmap":
        meta_g, metrics = tree_chunk(support, query, w)
    elif client_axis == "scan":
        def body(acc, inp):
            s, q, wi = inp
            g, met = algo.client_grad(phi, s, q)
            acc = tree_add(acc, tree_scale(
                jax.tree.map(lambda x: x.astype(jnp.float32), g), wi))
            return acc, met

        meta_g, mets = jax.lax.scan(body, tree_acc0(), (support, query, w))
        metrics = _weighted_metrics(w, mets)
    elif client_axis == "chunked":
        meta_g, metrics = _scan_chunks(
            tree_chunk, tree_acc0(), tree_add, support, query, w, m,
            client_chunk or min(m, 8))
    elif client_axis == "sharded":
        meta_g, metrics = _sharded_reduce(
            tree_chunk, tree_acc0(), tree_add, support, query, w, m,
            client_chunk, mesh, mesh_axis)
    else:
        raise ValueError(client_axis)

    new_phi, new_opt = optimizer.update(phi, meta_g, opt_state)
    return new_phi, new_opt, metrics


def _maybe_jit(step, jit: bool, donate: bool):
    if not jit:
        return step
    # buffer donation lets φ/opt-state update in place: the caller's
    # state is consumed by the call and must not be read again
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_meta_train_step(algo, optimizer, *, client_axis: str = "vmap",
                         client_chunk: int | None = None, mesh=None,
                         mesh_axis: str | None = None, jit: bool = True,
                         donate: bool = True):
    """-> step(state, support, query, weights) with state = {phi, opt}."""

    def step(state, support, query, weights=None):
        phi, opt_state, metrics = federated_meta_step(
            algo, optimizer, state["phi"], state["opt"], support, query,
            weights, client_axis=client_axis, client_chunk=client_chunk,
            mesh=mesh, mesh_axis=mesh_axis)
        return {"phi": phi, "opt": opt_state}, metrics

    return _maybe_jit(step, jit, donate)


# ---- packed parameter plane pipeline ------------------------------------

def init_packed_state(optimizer, plane: FlatPlane, phi, *, staleness=None,
                      clients_per_round=None, block_dtype=None,
                      compression=None, num_clients=None):
    """φ pytree -> {"phi": flat plane, "opt": flat optimizer state}.

    With ``staleness`` set (async_engine.StalenessConfig), the state
    additionally carries the in-flight straggler buffer: a
    ``(delay, k, N)`` ring of not-yet-arrived gradient rows plus their
    ``(delay, k)`` original aggregation weights, zero-initialized so
    the warmup rounds aggregate fresh rows only. With ``jitter`` on, the
    ring rows additionally carry their remaining-rounds counter ``c``
    and original drawn delay ``d`` (per-row γ^d on arrival).

    With ``compression`` set (kernels.meta_update.CompressionConfig)
    and error feedback on, the state carries the per-client residual
    plane: a ``(num_clients, N)`` f32 buffer of quantization errors not
    yet uploaded, zero-initialized (first participation compresses the
    raw gradient). It lives in train state, so checkpoints capture it
    and resumed runs replay bit-identically (DESIGN.md §17)."""
    from repro.optim.optimizers import make_flat_optimizer
    flat = plane.pack(phi)
    state = {"phi": flat, "opt": make_flat_optimizer(optimizer).init(flat)}
    if compression is not None and compression.error_feedback:
        if num_clients is None:
            raise ValueError("error feedback needs num_clients (total "
                             "train clients) to size the residual plane")
        state["ef"] = jnp.zeros((num_clients, plane.n_padded), jnp.float32)
    if staleness is not None:
        if clients_per_round is None:
            raise ValueError("staleness needs clients_per_round to size "
                             "the straggler buffer")
        k = staleness.num_stragglers(clients_per_round)
        bd = block_dtype or jnp.float32
        state["stale"] = {
            "G": jnp.zeros((staleness.delay, k, plane.n_padded), bd),
            "w": jnp.zeros((staleness.delay, k), jnp.float32)}
        if staleness.jitter:
            state["stale"]["c"] = jnp.zeros((staleness.delay, k), jnp.int32)
            state["stale"]["d"] = jnp.zeros((staleness.delay, k), jnp.int32)
    return state


def check_plane_composition(client_axis: str = "vmap", *,
                            aggregator: str = "mean", staleness=None,
                            faults=None, compression=None, dp=None):
    """Raise ``ValueError`` unless the packed step can build this
    combination of planes. The staleness ring, the failure plane
    (faults, a robust aggregator) and the bytes-on-the-wire plane
    (compression, DP) each need the full (m, N) gradient block before
    the reduce, so client_axis='vmap'; compression / DP exclude the
    other two. ``make_packed_meta_train_step`` and ``FederatedTrainer``
    both apply these rules; the trainer adds its own on top."""
    if aggregator not in mu_ops.AGGREGATORS:
        raise ValueError(f"unknown aggregator {aggregator!r}; expected "
                         f"one of {mu_ops.AGGREGATORS}")
    robust = aggregator != "mean"
    wire = compression is not None or dp is not None
    if client_axis != "vmap":
        for name, on in (("staleness-aware aggregation",
                          staleness is not None),
                         ("fault injection / robust aggregation",
                          faults is not None or robust),
                         ("compression / DP", wire)):
            if on:
                raise ValueError(f"{name} needs the full (m, N) gradient "
                                 f"block before the reduce — "
                                 f"client_axis='vmap' only")
    if wire and (staleness is not None or faults is not None or robust):
        raise ValueError("compression / DP compose with each other but "
                         "not with staleness, faults, or robust "
                         "aggregators — the codec/clip semantics of ring "
                         "rows and corrupted rows are undefined")


def make_packed_meta_train_step(algo, optimizer, plane: FlatPlane, *,
                                client_axis: str = "vmap",
                                client_chunk: int | None = None,
                                impl: str | None = None,
                                block_dtype=None,
                                staleness=None,
                                aggregator: str = "mean",
                                screen_factor: float = 3.0,
                                trim: int = 1,
                                faults=None,
                                guard: bool = False,
                                compression=None,
                                dp=None,
                                mesh=None, mesh_axis: str | None = None,
                                jit: bool = True, donate: bool = True):
    """Meta-train step over the packed plane: state = {phi: (N,), opt}.

    The inner loop runs on flat memory: each chunk of clients adapts in
    lockstep on a (C, N) client plane via the fused inner-update kernel,
    and per-client meta-gradients come out flat
    (``algo.client_grad_chunk_packed``); aggregation and the outer Adam
    stay on flat buffers too (DESIGN.md §9). ``impl`` picks xla / pallas
    / pallas_interpret for the fused kernels (None = the platform's
    pick, ``kernels/dispatch.py``). ``block_dtype`` sets the dtype of
    the client-gradient block (None = f32, exact; bfloat16 halves the
    aggregation traffic and models a half-precision client upload —
    the fused ops still accumulate in f32; see DESIGN.md §2).

    ``client_axis="sharded"`` splits clients over the devices of
    ``mesh`` (default: the ambient mesh); each device reduces its local
    block with the packed aggregation kernel and the (N,) partials are
    psum-reduced into the meta-gradient (DESIGN.md §10). Which planes
    below compose, and on which client axes, is decided by
    ``check_plane_composition``.

    ``staleness`` (async_engine.StalenessConfig; vmap axis only) turns
    on staleness-aware aggregation: the step takes an extra
    ``stale_sel=(straggler_idx, fresh_idx)`` input naming which of the
    round's clients straggle. Straggler rows of the (m, N) gradient
    block are detoured through the state's ``(delay, k, N)`` ring
    buffer and replaced in the aggregation by the rows that arrive
    this round — weighted by their original data-count weight times
    ``discount**delay`` and renormalized over the aggregated rows.
    Fresh and stale rows go through the SAME fused weighted-aggregate
    kernel, so the hot path stays one flat pass (DESIGN.md §12).

    The failure plane (DESIGN.md §14) adds four orthogonal knobs, all
    defaulting to off and all leaving the default graph bitwise
    untouched when off:

      * ``aggregator`` ∈ ``kernels.meta_update.ops.AGGREGATORS`` picks
        the (m, N) → (N,) reduction ("mean" = today's exact path;
        masked_mean / screen / trimmed are the robust modes — see
        ``robust_aggregate``). ``screen_factor``/``trim`` parameterize
        the screen threshold and per-coordinate trim count.
      * ``faults`` (federated.faults.FaultConfig; vmap axis only) makes
        the step take an extra per-round ``fault`` mask tuple and
        corrupts the gradient block *before* aggregation — dropped rows
        zero their weight, non-finite rows turn NaN, Byzantine rows are
        adversarially rewritten. Composes with ``staleness``: corrupted
        rows flow through the ring like honest ones.
      * ``guard`` turns on the fused non-finite check: one reduction
        over the flat meta-gradient; if anything is non-finite the
        round is *skipped* — φ and the optimizer state pass through
        unchanged (the staleness ring still advances: arrivals
        happened) — and the round's metrics carry ``skipped=1``.

    The bytes-on-the-wire plane (DESIGN.md §17) adds two more, both
    vmap-axis only and both bitwise no-ops when off:

      * ``compression`` (kernels.meta_update.CompressionConfig) encodes
        each client row of the (m, N) block — int8 per-row-scaled or
        top-k-sparsified — and aggregates the *encoded* uploads through
        the fused weighted kernel (dequantization folds into the
        weights / a scatter). With error feedback the step takes an
        extra ``ef_idx`` input (this round's picked-client indices into
        the state's ``(num_clients, N)`` residual plane): the residual
        rejoins the gradient before encoding and the new residual is
        scattered back. When the same client is picked twice in one
        round, the LAST row's residual wins (one upload channel per
        client per round).
      * ``dp`` (federated.privacy.DPConfig) applies the central-DP clip
        as aggregation-weight scaling — per-row norms are computed in
        the codec domain (s·‖q‖ / ‖topk values‖ / ‖g‖), so clipping
        composes with compression without decoding — and adds
        N(0, σ²·I) with σ = z·S/m to the aggregated meta-gradient
        (noise masked to the n_real live coordinates; the plane's
        alignment padding stays zero). The step then takes an extra
        per-round ``dp_key`` input (pure function of the round index —
        see ``DPConfig.round_key``).

    Composition order with both on: EF-correct → encode → clip (weight
    scale) → fused aggregate → noise (§17).
    """
    from repro.federated.faults import apply_faults
    from repro.federated.privacy import dp_clip_factors
    from repro.kernels.meta_update.compress import (int8_row_norms,
                                                    topk_encode,
                                                    topk_row_norms)
    from repro.optim.optimizers import make_flat_optimizer
    impl = mu_ops.resolve_impl(impl)
    flat_opt = make_flat_optimizer(optimizer, impl=impl)
    bd = block_dtype or jnp.float32
    check_plane_composition(client_axis, aggregator=aggregator,
                            staleness=staleness, faults=faults,
                            compression=compression, dp=dp)
    robust = aggregator != "mean"

    def aggregate(G, w_agg, *, prenorm):
        """The (m, N) → (N,) reduce. ``prenorm`` marks the staleness
        call sites whose historical mean path normalizes the weights
        itself — kept verbatim so mean mode stays bitwise identical."""
        if aggregator == "mean":
            if prenorm:
                w_agg = w_agg / jnp.sum(w_agg)
            return mu_ops.weighted_aggregate(G, w_agg, impl=impl)
        return mu_ops.robust_aggregate(
            G, w_agg, aggregator=aggregator, impl=impl,
            screen_factor=screen_factor, trim=trim)

    def finish(state, meta_g, metrics, extra=None):
        """Outer optimizer step + optional non-finite guard."""
        def update():
            if client_axis != "sharded":
                return flat_opt.update(state["phi"], meta_g, state["opt"])
            # φ and its optimizer state are replicated over the client
            # mesh, and a Pallas (Mosaic) kernel has no partitioning
            # rule: every device runs the fused update on its replica
            msh, _ = _resolve_mesh(mesh, mesh_axis)
            return jax.shard_map(
                flat_opt.update, mesh=msh, in_specs=P(), out_specs=P(),
                check_vma=False)(state["phi"], meta_g, state["opt"])

        if guard:
            # one fused reduce over the flat plane; skip-and-log round
            # semantics: a non-finite meta-gradient leaves φ AND the
            # optimizer state (incl. Adam's step count) untouched. A
            # cond, not a select over the outputs: the update then
            # compiles as in the unguarded step, so a clean run stays
            # bitwise identical (with a select, XLA recomputes the
            # moment update inside the φ fusion, which can round 1 ulp
            # differently)
            ok = jnp.all(jnp.isfinite(meta_g))
            new_flat, new_opt = jax.lax.cond(
                ok, update, lambda: (state["phi"], state["opt"]))
            metrics = {**metrics,
                       "skipped": jnp.logical_not(ok).astype(jnp.float32)}
        else:
            new_flat, new_opt = update()
        new_state = {"phi": new_flat, "opt": new_opt}
        if extra is not None:
            new_state.update(extra)
        return new_state, metrics

    def step(state, support, query, weights=None, stale_sel=None,
             fault=None, ef_idx=None, dp_key=None):
        phi = plane.unpack(state["phi"])
        m = jax.tree.leaves(support)[0].shape[0]
        w = _normalize_weights(weights, m)

        tplane = plane_for(phi["theta"])

        def chunk_grads(s, q):
            """(C, N) gradient rows + metrics for a chunk of clients,
            computed on the flat client plane."""
            G, mets = algo.client_grad_chunk_packed(
                plane, tplane, phi, s, q, impl=impl)
            return G.astype(bd), mets

        def packed_chunk(s, q, wc):
            """Fused (N,) weighted partial + weighted metrics for one
            chunk of clients."""
            G, mets = chunk_grads(s, q)
            return (mu_ops.weighted_aggregate(G, wc, impl=impl),
                    _weighted_metrics(wc, mets))

        if staleness is not None and staleness.jitter:
            # jittered stragglers: each ring row carries its own drawn
            # delay d ∈ [0, delay] and a remaining-rounds counter c; a
            # row rejoins the aggregation the round its counter hits 0
            # at weight w·γ^d (d = its ACTUAL staleness), then its
            # weight zeroes so it cannot arrive twice before falling
            # off the ring. d = 0 stragglers join their own round like
            # fresh rows (γ^0 = 1). The aggregation block is the m
            # current rows plus ALL delay·k ring rows — still static
            # shapes, still one pass through the fused kernel.
            strag, fresh, delays = stale_sel
            G, mets = chunk_grads(support, query)
            if faults is not None:
                G, w, w_rep = apply_faults(faults, G, w, fault)
            else:
                w_rep = w
            metrics = _weighted_metrics(w_rep, mets)
            buf = state["stale"]
            c = buf["c"] - 1
            arrive = (c <= 0) & (buf["w"] > 0)
            gamma_d = jnp.float32(staleness.discount) ** \
                buf["d"].astype(jnp.float32)
            arrived_w = jnp.where(arrive, buf["w"] * gamma_d, 0.0)
            dk = buf["G"].shape[0] * buf["G"].shape[1]
            agg_G = jnp.concatenate(
                [G[fresh], G[strag],
                 buf["G"].reshape(dk, buf["G"].shape[2])], axis=0)
            agg_w = jnp.concatenate(
                [w[fresh], jnp.where(delays == 0, w[strag], 0.0),
                 arrived_w.reshape(dk)], axis=0)
            meta_g = aggregate(agg_G, agg_w, prenorm=True)
            kept_w = jnp.where(arrive, 0.0, buf["w"])
            new_stale = {
                "G": jnp.concatenate([buf["G"][1:], G[strag][None]], axis=0),
                "w": jnp.concatenate(
                    [kept_w[1:],
                     jnp.where(delays > 0, w[strag], 0.0)[None]], axis=0),
                "c": jnp.concatenate([c[1:], delays[None]], axis=0),
                "d": jnp.concatenate([buf["d"][1:], delays[None]], axis=0)}
            return finish(state, meta_g, metrics, {"stale": new_stale})

        if staleness is not None:
            # straggler rows detour through the delay ring; arrived rows
            # (computed against φ from `delay` rounds ago) rejoin the
            # aggregation block at weight w·γ^delay — still one (m, N)
            # pass through the fused kernel
            strag, fresh = stale_sel
            G, mets = chunk_grads(support, query)
            if faults is not None:
                G, w, w_rep = apply_faults(faults, G, w, fault)
            else:
                w_rep = w
            metrics = _weighted_metrics(w_rep, mets)
            buf = state["stale"]
            arrived_w = buf["w"][0] * jnp.float32(
                staleness.discount ** staleness.delay)
            agg_G = jnp.concatenate([G[fresh], buf["G"][0]], axis=0)
            agg_w = jnp.concatenate([w[fresh], arrived_w], axis=0)
            meta_g = aggregate(agg_G, agg_w, prenorm=True)
            new_stale = {
                "G": jnp.concatenate([buf["G"][1:], G[strag][None]], axis=0),
                "w": jnp.concatenate([buf["w"][1:], w[strag][None]], axis=0)}
            return finish(state, meta_g, metrics, {"stale": new_stale})

        if compression is not None or dp is not None:
            # bytes-on-the-wire plane (§17): EF-correct -> encode ->
            # clip-as-weight-scale -> fused aggregate -> noise. Taken
            # only when a knob is on, so the default graphs below stay
            # bitwise identical.
            G, mets = chunk_grads(support, query)
            metrics = _weighted_metrics(w, mets)
            extra = None
            w_agg = w
            if compression is not None:
                corrected = G.astype(jnp.float32)
                if compression.error_feedback:
                    corrected = corrected + state["ef"][ef_idx]
                if compression.codec == "int8":
                    q, scales, resid = mu_ops.int8_encode(
                        corrected, impl=impl)
                    if dp is not None:
                        w_agg = w * dp_clip_factors(
                            int8_row_norms(q, scales), dp.clip_norm)
                    meta_g = mu_ops.int8_aggregate(
                        q, scales, w_agg, impl=impl)
                else:
                    vals, idx, resid = topk_encode(
                        corrected, compression.k_for(plane.n_real),
                        val_dtype=bd)
                    if dp is not None:
                        w_agg = w * dp_clip_factors(
                            topk_row_norms(vals), dp.clip_norm)
                    meta_g = mu_ops.topk_aggregate(
                        vals, idx, w_agg, plane.n_padded, impl=impl)
                if compression.error_feedback:
                    extra = {"ef": state["ef"].at[ef_idx].set(resid)}
            else:
                norms = jnp.sqrt(jnp.sum(
                    jnp.square(G.astype(jnp.float32)), axis=1))
                w_agg = w * dp_clip_factors(norms, dp.clip_norm)
                meta_g = mu_ops.weighted_aggregate(G, w_agg, impl=impl)
            if dp is not None and dp.noise_multiplier > 0:
                live = (jnp.arange(plane.n_padded)
                        < plane.n_real).astype(jnp.float32)
                meta_g = meta_g + jnp.float32(dp.sigma(m)) * live * \
                    jax.random.normal(dp_key, (plane.n_padded,),
                                      jnp.float32)
            return finish(state, meta_g, metrics, extra)

        if client_axis == "vmap" and (faults is not None or robust):
            # the failure plane needs the (m, N) block before the
            # reduce; taken only when a knob is on, so the default
            # vmap graph below stays bitwise identical
            G, mets = chunk_grads(support, query)
            if faults is not None:
                G, w_agg, w_rep = apply_faults(faults, G, w, fault)
            else:
                w_agg = w_rep = w
            metrics = _weighted_metrics(w_rep, mets)
            meta_g = aggregate(G, w_agg, prenorm=False)
        elif client_axis == "vmap":
            meta_g, metrics = packed_chunk(support, query, w)
        elif client_axis == "scan":
            def body(acc, inp):
                s, q, wi = inp
                G, met = chunk_grads(
                    *jax.tree.map(lambda x: x[None], (s, q)))
                g, met = G[0], jax.tree.map(lambda x: x[0], met)
                return acc + wi * g.astype(jnp.float32), met

            meta_g, mets = jax.lax.scan(
                body, plane.zeros(), (support, query, w))
            metrics = _weighted_metrics(w, mets)
        elif client_axis == "chunked":
            meta_g, metrics = _scan_chunks(
                packed_chunk, plane.zeros(), jnp.add, support, query, w,
                m, client_chunk or min(m, 8))
        elif client_axis == "sharded":
            meta_g, metrics = _sharded_reduce(
                packed_chunk, plane.zeros(), jnp.add, support, query, w,
                m, client_chunk, mesh, mesh_axis)
        else:
            raise ValueError(client_axis)

        return finish(state, meta_g, metrics)

    return _maybe_jit(step, jit, donate)
