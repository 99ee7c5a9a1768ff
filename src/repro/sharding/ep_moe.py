"""Expert-parallel MoE with explicit all-to-all (beyond-paper §Perf
optimization).

Baseline (models/moe.py) is tensor-parallel: every device holds a slice
of EVERY expert's weights, tokens stay data-local, and each expert matmul
all-reduces over the model axis. For fine-grained-expert models
(DeepSeek-V2: 160 experts of d_ff=1536) the TP slice per device is
1536/16 = 96 columns — far below MXU efficiency — and router dispatch
is replicated work.

This variant shards EXPERTS over the model axis (E_local = E / 16 per
device) inside a shard_map:
  1. local top-k routing,
  2. capacity-bucketed dispatch tensors (tokens_local, E, C_local),
  3. all_to_all over the model axis moves token buckets to expert owners,
  4. dense local expert FFN at full d_ff width (MXU-aligned),
  5. reverse all_to_all + weighted combine.

Collective cost: 2 x all_to_all of (tokens * k * d) bytes over the model
axis, replacing per-layer all-reduces of the full activation. See
EXPERIMENTS.md §Perf hillclimb #2 for the measured delta.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.layers import mlp_apply
from repro.models.moe import top_k_gates


def _local_dispatch(cfg, xt, logits, capacity):
    """Token->expert dispatch on one shard. xt: (T, d). Gating is the
    config's (`models/moe.top_k_gates`)."""
    T, d = xt.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    gates, expert_ids = top_k_gates(cfg, logits)
    flat_expert = expert_ids.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gates.reshape(-1)
    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    sorted_token = flat_token[order]
    sorted_gate = flat_gate[order]
    counts = jnp.bincount(sorted_expert, length=E)
    start = jnp.cumsum(counts) - counts
    rank = jnp.arange(T * K) - start[sorted_expert]
    keep = rank < capacity
    slot = sorted_expert * capacity + jnp.where(keep, rank, 0)
    buf_tok = jnp.zeros((E * capacity,), jnp.int32).at[slot].set(
        jnp.where(keep, sorted_token, 0).astype(jnp.int32))
    buf_mask = jnp.zeros((E * capacity,), jnp.float32).at[slot].set(
        keep.astype(jnp.float32))
    x_e = (xt[buf_tok] * buf_mask[:, None]).reshape(E, capacity, d)
    return x_e, (sorted_token, sorted_gate, keep, slot)


def ep_moe_apply(params, cfg, x, mesh, *, capacity_factor=None):
    """Expert-parallel MoE layer. x: (B, L, d) sharded (data, None, None).

    Expert weights must be sharded P("model", None, None) — E over the
    model axis. Requires E % model_axis == 0.
    """
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    m_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    assert E % m_size == 0, (E, m_size)
    cf = capacity_factor or cfg.capacity_factor
    B, L, d = x.shape

    def local_fn(x_local, w_router, w_gate, w_up, w_down):
        # x_local: (B/dp, L, d); expert weights: (E/mp, d, ff)
        Bl = x_local.shape[0]
        T = Bl * L
        xt = x_local.reshape(T, d)
        logits = (xt @ w_router).astype(jnp.float32)
        capacity = int(np.ceil(T * K / E * cf))
        x_e, (sorted_token, sorted_gate, keep, slot) = _local_dispatch(
            cfg, xt, logits, capacity)
        # all_to_all (tiled): (E, C, d) -> (E/mp, C*mp, d): expert axis
        # split across the model axis, token buckets concatenated at the
        # expert owner
        x_recv = jax.lax.all_to_all(x_e, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
        # local experts at FULL width
        h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_recv, w_gate))
             * jnp.einsum("ecd,edf->ecf", x_recv, w_up))
        y_e = jnp.einsum("ecf,efd->ecd", h, w_down)
        # reverse all_to_all: (E/mp, C*mp, d) -> (E, C, d)
        y_back = jax.lax.all_to_all(y_e, "model", split_axis=1,
                                    concat_axis=0, tiled=True)
        y_flat = y_back.reshape(E * capacity, d)
        contrib = jnp.zeros((T, d), y_flat.dtype).at[
            jnp.where(keep, sorted_token, T)
        ].add(jnp.where(keep, sorted_gate, 0.0)[:, None].astype(y_flat.dtype)
              * y_flat[jnp.where(keep, slot, 0)], mode="drop")
        return contrib.reshape(Bl, L, d).astype(x_local.dtype)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P("data", None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=P("data", None, None), check_vma=False)
    y = fn(x, params["w_router"], params["w_gate"], params["w_up"],
           params["w_down"])
    if cfg.num_shared_experts > 0:
        y = y + mlp_apply(params["shared"], x, cfg.mlp_act)
    return y
