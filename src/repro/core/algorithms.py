"""The paper's contribution: gradient-based meta-learning algorithms in
the federated setting (Algorithm 1 of the paper).

Every algorithm maintains server-side *algorithm parameters* φ and
implements the client-side procedure ModelTraining(φ; D_S, D_Q) -> g_u:

  MAML      φ = {theta};          inner: θ_u = θ − α∇L_S(θ);
            g = ∇_θ L_Q(θ_u)      (second-order, differentiates through
                                   the inner update)
  FOMAML    same, but g = ∇_{θ_u} L_Q(θ_u)  (first-order approximation)
  Meta-SGD  φ = {theta, alpha};   inner: θ_u = θ − α ∘ ∇L_S(θ) with
            per-coordinate learnable α; g = ∇_{(θ,α)} L_Q(θ_u)
  Reptile   φ = {theta};          client runs k SGD steps on local data;
            g = θ − θ_k           (beyond-paper extra; Nichol et al. '18)

`adapt` is the deployment path (paper §3.2 last ¶): update θ on a new
client's support set and predict with θ_u.

Two executions of the inner loop:

- tree (``_inner_adapt`` / ``client_grad``): θ stays a pytree; the
  update runs per-leaf. Default, works everywhere.
- client plane (``_inner_adapt_plane`` / ``client_grad_chunk_packed``):
  a chunk of C clients adapts in lockstep on a flat (C, N) plane
  (``utils/flat.py``); each inner step is one vmapped model gradient
  plus ONE fused update over the whole chunk
  (``kernels/meta_update/ops.inner_update``), instead of per-client
  per-leaf op soup. Per-client meta-gradients come out flat — grad of
  the summed chunk meta-loss w.r.t. the per-client (C, N) plane is
  exactly the stack of per-client gradients, because row c only enters
  client c's loss — so the (m, N) aggregation block never goes through
  a pytree. See DESIGN.md §9.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.meta_update import ops as mu_ops
from repro.models.layers import Rng
from repro.utils.flat import plane_for


def _order_scope(order: int):
    """What a client gradient traces in: second order differentiates
    the inner gradient again, which kernels with a first-order backward
    only cannot serve (``kernels/dispatch.second_order``)."""
    return dispatch.second_order() if order == 2 else contextlib.nullcontext()


def _inner_adapt(loss_fn, theta, alpha, support, steps: int,
                 second_order: bool):
    """k gradient steps on the support set (unrolled so reverse-mode
    differentiation through the update is possible for MAML/Meta-SGD)."""
    for _ in range(steps):
        g = jax.grad(loss_fn)(theta, support)
        if not second_order:
            g = jax.lax.stop_gradient(g)
        theta = mu_ops.meta_update(theta, alpha, g)
    return theta


# ---- client-plane (packed) inner loop -----------------------------------

def _flat_fn(fn, plane):
    """Lift ``fn(params_tree, batch)`` to flat θ (static slices, no
    FLOPs; autodiff through the unpack yields flat gradients —
    ``unpack_ad`` so each backward pass emits one concat, not L
    zero-padded planes)."""
    def flat(theta_flat, batch):
        return fn(plane.unpack_ad(theta_flat), batch)
    return flat


def _inner_adapt_plane(loss_fn, tplane, Theta, alpha, support, steps: int,
                       second_order: bool, impl):
    """k fused gradient steps for a chunk of clients in lockstep.

    Theta: (C, N) client plane; support leaves carry a leading C axis.
    alpha: python scalar, shared (N,), or per-client (C, N) flat rates.
    Unrolled like ``_inner_adapt``; the fused update's custom VJP keeps
    the whole loop reverse-differentiable for second-order algorithms.
    """
    flat_loss = _flat_fn(loss_fn, tplane)
    for _ in range(steps):
        G = jax.vmap(jax.grad(flat_loss))(Theta, support)
        if not second_order:
            G = jax.lax.stop_gradient(G)
        Theta = mu_ops.inner_update(Theta, alpha, G, impl=impl)
    return Theta


def _broadcast_plane(flat, C):
    return jnp.broadcast_to(flat, (C, flat.shape[-1]))


def _chunk_len(tree):
    return jax.tree.leaves(tree)[0].shape[0]


def _assemble_phi_rows(pplane, tplane, parts: dict):
    """Per-part flat (C, tplane.n_padded) grads -> (C, pplane.n_padded)
    rows in φ-plane layout.

    φ is a flat dict whose values are each structurally identical to θ
    (e.g. Meta-SGD's {"alpha", "theta"}), so the φ plane is the sorted-
    key concatenation of each part's real region, plus alignment pad —
    pure slice/concat on flat buffers, no pytree round-trip."""
    assert pplane.n_real == len(parts) * tplane.n_real, \
        (pplane.n_real, tplane.n_real, sorted(parts))
    body = jnp.concatenate(
        [parts[k][..., :tplane.n_real] for k in sorted(parts)], axis=-1)
    pad = pplane.n_padded - body.shape[-1]
    if pad:
        body = jnp.pad(body, ((0, 0), (0, pad)))
    return body


@dataclasses.dataclass
class MetaAlgorithm:
    """Common interface; see factory classes below."""
    name: str
    loss_fn: Callable                     # (params, batch) -> scalar
    eval_fn: Callable                     # (params, batch) -> (loss, metrics)
    inner_lr: float
    inner_steps: int = 1

    # ---- subclass hooks -------------------------------------------------
    def init_state(self, key, model_init: Callable):
        raise NotImplementedError

    def client_grad(self, phi, support, query):
        """ModelTraining on one client: returns (g_u matching φ, metrics)."""
        raise NotImplementedError

    def client_grad_chunk_packed(self, pplane, tplane, phi, support, query,
                                 *, impl=None):
        """ModelTraining for a chunk of C clients on the flat client
        plane: support/query leaves carry a leading C axis; returns
        (G: (C, pplane.n_padded) f32 rows matching the φ plane, metrics
        with leading C)."""
        raise NotImplementedError

    def adapt(self, phi, support, steps: int | None = None):
        """Deployment: adapt θ to a new client's support set."""
        alpha = phi.get("alpha", self.inner_lr)
        return _inner_adapt(self.loss_fn, phi["theta"], alpha, support,
                            steps or self.inner_steps, second_order=False)

    def adapt_packed(self, phi, support, steps: int | None = None, *,
                     impl=None, plane=None):
        """Deployment on the packed plane: same math as ``adapt`` but the
        inner loop runs fused over flat θ (paper §3.2). Returns the
        adapted θ as a pytree."""
        tplane = plane or plane_for(phi["theta"])
        sup = jax.tree.map(lambda x: x[None], support)
        Theta = self.adapt_packed_batch(phi, sup, steps, impl=impl,
                                        plane=tplane)
        return tplane.unpack(Theta[0])

    def adapt_packed_batch(self, phi, supports, steps: int | None = None, *,
                           impl=None, plane=None):
        """Deployment at serving scale: C concurrent clients adapt in
        lockstep on the flat (C, N) client plane — the same fused
        ``inner_update_plane`` kernel that powers training. ``supports``
        leaves carry a leading C axis (client c's support set is row c).
        Rows are independent — row c only enters client c's loss — so
        each adapted row is bit-identical to that client's solo
        ``adapt``/``adapt_packed`` (the serving plane's contract,
        pinned by tests/test_serving.py). Returns the adapted
        (C, n_padded) plane; rows unpack via ``plane_for(phi["theta"])``.
        """
        tplane = plane or plane_for(phi["theta"])
        C = _chunk_len(supports)
        Theta = _broadcast_plane(tplane.pack(phi["theta"]), C)
        alpha = phi.get("alpha")
        alpha = self.inner_lr if alpha is None else tplane.pack(alpha)
        return _inner_adapt_plane(self.loss_fn, tplane, Theta, alpha,
                                  supports, steps or self.inner_steps,
                                  second_order=False, impl=impl)

    def query_metrics(self, phi, support, query):
        theta_u = self.adapt(phi, support)
        loss, m = self.eval_fn(theta_u, query)
        return {"query_loss": loss, **m}


class MAML(MetaAlgorithm):
    def __init__(self, loss_fn, eval_fn, inner_lr, inner_steps=1, order=2,
                 name=None):
        super().__init__(name or ("maml" if order == 2 else "fomaml"),
                         loss_fn, eval_fn, inner_lr, inner_steps)
        assert order in (1, 2)
        self.order = order

    def init_state(self, key, model_init):
        return {"theta": model_init(key)}

    def client_grad(self, phi, support, query):
        def meta_loss(theta):
            theta_u = _inner_adapt(self.loss_fn, theta, self.inner_lr,
                                   support, self.inner_steps,
                                   second_order=(self.order == 2))
            return self.eval_fn(theta_u, query)

        if self.order == 2:
            with dispatch.second_order():
                (loss, metrics), g = jax.value_and_grad(
                    meta_loss, has_aux=True)(phi["theta"])
        else:
            # FOMAML: gradient at the adapted parameters
            theta_u = _inner_adapt(self.loss_fn, phi["theta"], self.inner_lr,
                                   support, self.inner_steps,
                                   second_order=False)
            (loss, metrics), g = jax.value_and_grad(
                self.eval_fn, has_aux=True)(theta_u, query)
        return {"theta": g}, {"query_loss": loss, **metrics}

    def client_grad_chunk_packed(self, pplane, tplane, phi, support, query,
                                 *, impl=None):
        # φ = {"theta"}: the φ plane IS the θ plane (same leaves, order)
        assert pplane.n_padded == tplane.n_padded, \
            (pplane.n_padded, tplane.n_padded)
        C = _chunk_len(support)
        Theta0 = _broadcast_plane(tplane.pack(phi["theta"]), C)
        flat_eval = _flat_fn(self.eval_fn, tplane)
        if self.order == 2:
            def chunk_meta_loss(Theta):
                Theta_u = _inner_adapt_plane(
                    self.loss_fn, tplane, Theta, self.inner_lr, support,
                    self.inner_steps, second_order=True, impl=impl)
                losses, mets = jax.vmap(flat_eval)(Theta_u, query)
                return jnp.sum(losses), (losses, mets)

            with dispatch.second_order():
                G, (losses, mets) = jax.grad(chunk_meta_loss,
                                             has_aux=True)(Theta0)
        else:
            Theta_u = _inner_adapt_plane(
                self.loss_fn, tplane, Theta0, self.inner_lr, support,
                self.inner_steps, second_order=False, impl=impl)

            def one(t, q):
                (loss, met), g = jax.value_and_grad(
                    flat_eval, has_aux=True)(t, q)
                return g, loss, met

            G, losses, mets = jax.vmap(one)(Theta_u, query)
        return G, {"query_loss": losses, **mets}


def FOMAML(loss_fn, eval_fn, inner_lr, inner_steps=1):
    return MAML(loss_fn, eval_fn, inner_lr, inner_steps, order=1)


class MetaSGD(MetaAlgorithm):
    def __init__(self, loss_fn, eval_fn, inner_lr, inner_steps=1, order=2):
        super().__init__("meta-sgd" if order == 2 else "meta-sgd-fo",
                         loss_fn, eval_fn, inner_lr, inner_steps)
        self.order = order

    def init_state(self, key, model_init):
        k1, k2 = jax.random.split(jax.random.PRNGKey(key)
                                  if isinstance(key, int) else key)
        theta = model_init(k1)
        # α initialized around inner_lr with small random spread (paper [12])
        rng = Rng(k2)
        alpha = jax.tree.map(
            lambda p: self.inner_lr * (0.5 + jax.random.uniform(
                rng.next(), p.shape, jnp.float32)),
            theta)
        return {"theta": theta, "alpha": alpha}

    def client_grad(self, phi, support, query):
        def meta_loss(phi_):
            theta_u = _inner_adapt(self.loss_fn, phi_["theta"], phi_["alpha"],
                                   support, self.inner_steps,
                                   second_order=(self.order == 2))
            return self.eval_fn(theta_u, query)

        with _order_scope(self.order):
            (loss, metrics), g = jax.value_and_grad(meta_loss,
                                                    has_aux=True)(phi)
        return g, {"query_loss": loss, **metrics}

    def client_grad_chunk_packed(self, pplane, tplane, phi, support, query,
                                 *, impl=None):
        C = _chunk_len(support)
        Theta0 = _broadcast_plane(tplane.pack(phi["theta"]), C)
        # per-client α copies so grad w.r.t. the (C, N) block is the
        # per-client α-gradient, not the chunk sum
        Alpha0 = _broadcast_plane(tplane.pack(phi["alpha"]), C)
        flat_eval = _flat_fn(self.eval_fn, tplane)

        def chunk_meta_loss(Theta, Alpha):
            Theta_u = _inner_adapt_plane(
                self.loss_fn, tplane, Theta, Alpha, support,
                self.inner_steps, second_order=(self.order == 2), impl=impl)
            losses, mets = jax.vmap(flat_eval)(Theta_u, query)
            return jnp.sum(losses), (losses, mets)

        with _order_scope(self.order):
            (_, (losses, mets)), (gT, gA) = jax.value_and_grad(
                chunk_meta_loss, argnums=(0, 1), has_aux=True)(Theta0,
                                                               Alpha0)
        G = _assemble_phi_rows(pplane, tplane, {"theta": gT, "alpha": gA})
        return G, {"query_loss": losses, **mets}


class Reptile(MetaAlgorithm):
    """Beyond-paper extra: first-order, no support/query split needed."""

    def __init__(self, loss_fn, eval_fn, inner_lr, inner_steps=3):
        super().__init__("reptile", loss_fn, eval_fn, inner_lr, inner_steps)

    def init_state(self, key, model_init):
        return {"theta": model_init(key)}

    def client_grad(self, phi, support, query):
        theta_k = _inner_adapt(self.loss_fn, phi["theta"], self.inner_lr,
                               support, self.inner_steps, second_order=False)
        # one extra pass over the query set (uses all local data, like the
        # original Reptile which has no support/query distinction)
        theta_k = _inner_adapt(self.loss_fn, theta_k, self.inner_lr, query,
                               1, second_order=False)
        g = jax.tree.map(lambda a, b: (a - b).astype(jnp.float32),
                         phi["theta"], theta_k)
        loss, metrics = self.eval_fn(theta_k, query)
        return {"theta": g}, {"query_loss": loss, **metrics}

    def client_grad_chunk_packed(self, pplane, tplane, phi, support, query,
                                 *, impl=None):
        assert pplane.n_padded == tplane.n_padded, \
            (pplane.n_padded, tplane.n_padded)
        C = _chunk_len(support)
        Theta0 = _broadcast_plane(tplane.pack(phi["theta"]), C)
        Theta_k = _inner_adapt_plane(
            self.loss_fn, tplane, Theta0, self.inner_lr, support,
            self.inner_steps, second_order=False, impl=impl)
        Theta_k = _inner_adapt_plane(
            self.loss_fn, tplane, Theta_k, self.inner_lr, query, 1,
            second_order=False, impl=impl)
        G = (Theta0 - Theta_k).astype(jnp.float32)
        losses, mets = jax.vmap(_flat_fn(self.eval_fn, tplane))(Theta_k,
                                                               query)
        return G, {"query_loss": losses, **mets}


def make_algorithm(name: str, loss_fn, eval_fn, inner_lr: float,
                   inner_steps: int = 1) -> MetaAlgorithm:
    name = name.lower()
    if name == "maml":
        return MAML(loss_fn, eval_fn, inner_lr, inner_steps, order=2)
    if name == "fomaml":
        return MAML(loss_fn, eval_fn, inner_lr, inner_steps, order=1)
    if name in ("meta-sgd", "metasgd"):
        return MetaSGD(loss_fn, eval_fn, inner_lr, inner_steps, order=2)
    if name in ("meta-sgd-fo", "metasgd-fo"):
        return MetaSGD(loss_fn, eval_fn, inner_lr, inner_steps, order=1)
    if name == "reptile":
        return Reptile(loss_fn, eval_fn, inner_lr, inner_steps)
    raise ValueError(f"unknown algorithm {name!r}")
