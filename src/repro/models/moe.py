"""Mixture-of-Experts FFN: Mixtral-style capacity dispatch, and DeepSeek
gating over a layer that is told which experts it holds.

Baseline sharding story (tensor-parallel experts): stacked expert weights
(E, d, d_ff) are sharded on d/d_ff over ("data","model"); dispatch keeps
tokens shard-local. An expert-parallel all-to-all variant lives in
`repro/sharding/ep_moe.py` as the §Perf optimization.

Dispatch algorithm (jit-stable shapes, standard Switch-style capacity):
  1. router logits -> top-k experts + renormalized gates (Mixtral style),
  2. flatten (token, slot) pairs, stable-sort by expert id,
  3. within-expert rank via cumsum; tokens with rank >= capacity drop,
  4. gather tokens into (E, capacity, d), run all experts as one batched
     einsum (MXU-friendly), scatter-add back weighted by gates.

Also computes the Switch/ST-MoE load-balance auxiliary loss — kept inside
both FedMeta loops so the router adapts per client.

DeepSeek gating (`router_scoring="softmax"`, `held_moe_apply`, DESIGN.md
§20): softmax over all `num_experts` router logits in float32, the top-k
probabilities (renormalized iff `norm_topk_prob`, else times
`routed_scaling_factor`), and the sequence-level aux loss of the
release. The layer holds `experts_held` experts from `first_expert` —
one chip's share when experts are split over chips — and computes their
part of the result only: the (token, slot) pairs routed to them, sorted
by expert, run as one grouped matrix product over ragged groups
(`kernels/grouped_matmul`) of static total size T·min(k, held), with
no capacity and no drops. What the other experts would add is left out.
The shared experts are added once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grouped_matmul import ops as gmm_ops
from repro.models.layers import Rng, dense_init, mlp_apply, mlp_init

# the step's routing counters, summed over layers and passes
# (moe_load_max: the largest over them)
STATS = ("moe_pairs_held", "moe_load_max", "moe_dropped")


def moe_init(rng: Rng, cfg, dtype):
    """Router over all `num_experts`; stacked weights of the held ones."""
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.held_experts
    p = {"w_router": dense_init(rng, d, cfg.num_experts, dtype)}
    # stacked expert weights: (E, ...) so experts run as one batched matmul
    def stack(maker):
        return jnp.stack([maker() for _ in range(E)])
    if cfg.mlp_act == "swiglu":
        p["w_gate"] = stack(lambda: dense_init(rng, d, ff, dtype))
    p["w_up"] = stack(lambda: dense_init(rng, d, ff, dtype))
    p["w_down"] = stack(lambda: dense_init(rng, ff, d, dtype))
    if cfg.num_shared_experts > 0:
        p["shared"] = mlp_init(rng, d, ff * cfg.num_shared_experts,
                               cfg.mlp_act, dtype)
    return p


def _expert_ffn(params, cfg, x_e):
    """x_e: (E, C, d) -> (E, C, d): all experts as batched einsums."""
    if cfg.mlp_act == "swiglu":
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, params["w_gate"]))
             * jnp.einsum("ecd,edf->ecf", x_e, params["w_up"]))
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(jnp.einsum("ecd,edf->ecf", x_e,
                                              params["w_up"])))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x_e, params["w_up"]))
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def top_k_gates(cfg, logits):
    """Router logits (T, E) f32 -> (gates (T, K) f32, experts (T, K)).

    "topk_softmax" (Mixtral): the top-k logits, softmax over those k.
    "softmax" (DeepSeek): softmax over all E, the top-k probabilities,
    renormalized to sum 1 iff `norm_topk_prob`, else times
    `routed_scaling_factor`."""
    K = cfg.num_experts_per_tok
    if cfg.router_scoring == "topk_softmax":
        gate_vals, expert_ids = jax.lax.top_k(logits, K)
        return jax.nn.softmax(gate_vals, axis=-1), expert_ids
    gates, expert_ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    if K > 1 and cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    else:
        gates = gates * cfg.routed_scaling_factor
    return gates, expert_ids


def moe_apply(params, cfg, x, *, capacity_factor: float | None = None):
    """x: (B, L, d) -> (y, aux_loss)."""
    B, L, d = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    T = B * L
    xt = x.reshape(T, d)

    logits = (xt @ params["w_router"]).astype(jnp.float32)       # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = top_k_gates(cfg, logits)                 # (T, K)

    # ---- load-balance aux loss (Switch): E * mean(frac_tokens * mean_prob)
    onehot = jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32)
    frac_tokens = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * mean_prob) * cfg.router_aux_coef

    # ---- capacity dispatch
    capacity = int(np.ceil(T * K / E * cf))
    flat_expert = expert_ids.reshape(-1)                          # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    # rank within expert group
    counts = jnp.bincount(sorted_expert, length=E)
    group_start = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - group_start[sorted_expert]
    keep = rank < capacity
    slot = sorted_expert * capacity + jnp.where(keep, rank, 0)

    # gather tokens -> (E*capacity, d); dropped slots read token 0, masked
    buf_tok = jnp.zeros((E * capacity,), jnp.int32).at[slot].set(
        jnp.where(keep, sorted_token, 0).astype(jnp.int32))
    buf_mask = jnp.zeros((E * capacity,), jnp.float32).at[slot].set(
        keep.astype(jnp.float32))
    x_e = (xt[buf_tok] * buf_mask[:, None]).reshape(E, capacity, d)

    y_e = _expert_ffn(params, cfg, x_e).reshape(E * capacity, d)

    # combine: scatter-add weighted outputs back to tokens
    contrib = jnp.zeros((T, d), y_e.dtype).at[
        jnp.where(keep, sorted_token, T)  # dropped -> scratch row T
    ].add(jnp.where(keep, sorted_gate, 0.0)[:, None].astype(y_e.dtype)
          * y_e[jnp.where(keep, slot, 0)],
          mode="drop")
    y = contrib.reshape(B, L, d)

    if cfg.num_shared_experts > 0:
        y = y + mlp_apply(params["shared"], x, cfg.mlp_act)
    return y.astype(x.dtype), aux


# ------------------------------------------------- DeepSeek, held experts

def router_logits(params, xt):
    """(T, d) -> (T, E) router logits, a float32 product at full
    precision (DeepSeek's gate runs in float32)."""
    return jnp.dot(xt.astype(jnp.float32),
                   params["w_router"].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def seq_aux_loss(cfg, probs, expert_ids, B: int, L: int):
    """DeepSeek's sequence-level balance loss: per sequence, the share of
    its (token, slot) pairs each expert got, over the even share
    k/E, times the expert's mean probability; summed over experts,
    averaged over sequences, times the coefficient."""
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    counts = jax.vmap(lambda ids: jnp.bincount(ids, length=E))(
        expert_ids.reshape(B, L * K)).astype(jnp.float32)
    ce = counts / (L * K / E)
    mean_prob = probs.reshape(B, L, E).mean(axis=1)
    return jnp.mean(jnp.sum(ce * mean_prob, axis=-1)) * cfg.router_aux_coef


def held_pairs(cfg, expert_ids, T: int):
    """The (token, slot) pairs routed to the held experts, sorted by
    expert -> (order (M,) pair index, sizes (held,) int32, dropped).

    M = T * min(k, held) rows bound the held pairs (a token picks an
    expert at most once). Pairs of other experts sort after them. No
    pair is dropped: `dropped` is 0, the counter that shows it."""
    K, H = cfg.num_experts_per_tok, cfg.held_experts
    local = expert_ids.reshape(-1) - cfg.first_expert            # (T*K,)
    key = jnp.where((local >= 0) & (local < H), local, H)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
    return order[:T * min(K, H)], sizes, jnp.zeros((), jnp.int32)


def held_moe_apply(params, cfg, x):
    """x: (B, L, d) -> (y, aux_loss, stats): DeepSeek gating over all
    experts, the held experts' part of the routed output, and the
    shared experts. stats: `STATS`, int32."""
    B, L, d = x.shape
    K = cfg.num_experts_per_tok
    T = B * L
    xt = x.reshape(T, d)
    logits = router_logits(params, xt)                           # (T, E)
    gates, expert_ids = top_k_gates(cfg, logits)
    aux = seq_aux_loss(cfg, jax.nn.softmax(logits, axis=-1), expert_ids,
                       B, L)

    order, sizes, dropped = held_pairs(cfg, expert_ids, T)
    n_held = jnp.sum(sizes)
    valid = jnp.arange(order.shape[0]) < n_held
    token = order // K
    gate = jnp.where(valid, gates.reshape(-1)[order], 0.0)
    # rows past the held pairs read zeros, so no gradient reaches them
    x_sorted = jnp.where(valid[:, None], xt[token], jnp.zeros((), x.dtype))
    if cfg.mlp_act == "swiglu":
        h = (jax.nn.silu(gmm_ops.gmm(x_sorted, params["w_gate"], sizes))
             * gmm_ops.gmm(x_sorted, params["w_up"], sizes))
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(gmm_ops.gmm(x_sorted, params["w_up"],
                                               sizes)))
    else:
        h = jax.nn.gelu(gmm_ops.gmm(x_sorted, params["w_up"], sizes))
    y_sorted = gmm_ops.gmm(h, params["w_down"], sizes)           # (M, d)
    y = jnp.zeros((T, d), jnp.float32).at[token].add(
        gate[:, None] * y_sorted.astype(jnp.float32))
    y = y.reshape(B, L, d).astype(x.dtype)
    if cfg.num_shared_experts > 0:
        y = y + mlp_apply(params["shared"], x, cfg.mlp_act)
    stats = {"moe_pairs_held": n_held, "moe_load_max": jnp.max(sizes),
             "moe_dropped": dropped}
    return y, aux, stats


def zero_stats(cfg) -> dict:
    """The routing counters' zeros where a layer of `cfg` counts them
    (DeepSeek gating), else {} (nothing rides the layer loop)."""
    if cfg.num_experts == 0 or cfg.router_scoring != "softmax":
        return {}
    return {k: jnp.zeros((), jnp.int32) for k in STATS}


def merge_stats(a: dict, b: dict) -> dict:
    """Counters of two layers or passes: sums, and the larger load."""
    if not a:
        return b
    if not b:
        return a
    return {k: (jnp.maximum(a[k], b[k]) if k == "moe_load_max"
                else a[k] + b[k]) for k in STATS}
