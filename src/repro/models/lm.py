"""Causal LM assembly: embeddings -> (lead blocks; scanned layer stacks)
-> final norm -> logits. Supports every assigned architecture family:

- homogeneous dense/MoE/SSM stacks: one scanned stack (fast compile),
- hybrid (jamba): scan over repetitions of the layer *pattern period*,
- first_k_dense (deepseek-v2): leading layers unrolled,
- encoder-decoder (seamless): bidirectional encoder over modality frames
  + decoder with cross-attention,
- modality stubs (vlm/audio): precomputed embeddings enter through
  `mod_proj` (the one sanctioned stub — no ViT/conformer here),
- M-RoPE position synthesis for vlm prefix+text layout.

Entry points:
  init_lm            parameter init
  lm_apply           training / prefill forward (optionally emits cache)
  init_decode_cache  decode cache pytree
  lm_decode_step     one-token decode against the cache

With remat, the training forward's layer scan recomputes each layer in
the backward but saves the flash-attention forward's output and
log-sum-exp (``flash_attention.RESIDUAL_O``, ``RESIDUAL_LSE``): they
are all the kernels' backward needs beyond q, k and v, and they come
from the costliest op per FLOP in the layer, where q, k and v cost a
norm, the projections and RoPE. Where attention runs XLA (second
order) those names never appear and the remat saves nothing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.attention.flash_attention import (RESIDUAL_LSE,
                                                     RESIDUAL_O)
from repro.models.blocks import (block_decode, block_forward, block_init,
                                 block_init_cache, block_prefill, layer_spec)
from repro.models.moe import merge_stats, zero_stats
from repro.models.layers import (Rng, dense_init, embed_init, rmsnorm,
                                 rmsnorm_init, text_mrope_positions)


# ---------------------------------------------------------------- grouping

def layer_groups(cfg):
    """-> (lead_specs, period_specs, n_reps): lead layers are unrolled,
    the rest is a scanned stack of `n_reps` repetitions of the period."""
    specs = [layer_spec(cfg, i) for i in range(cfg.num_layers)]
    lead = specs[:cfg.first_k_dense]
    rest = specs[cfg.first_k_dense:]
    P = len(cfg.layer_pattern)
    if cfg.num_experts > 0:
        P = math.lcm(P, cfg.moe_layer_period)
    assert len(rest) % P == 0, (cfg.name, len(rest), P)
    for i, s in enumerate(rest):
        assert s == rest[i % P], f"{cfg.name}: aperiodic layer stack"
    return lead, rest[:P], len(rest) // P


# ---------------------------------------------------------------- init

def init_lm(key, cfg):
    rng = Rng(key)
    dtype = jnp.dtype(cfg.dtype)
    d, vocab = cfg.d_model, cfg.vocab_size
    params = {"embed": embed_init(rng, vocab, d, dtype)}
    lead, period, n_reps = layer_groups(cfg)
    for i, spec in enumerate(lead):
        params[f"lead_{i}"] = block_init(rng, cfg, spec, dtype)
    stack = {}
    for j, spec in enumerate(period):
        reps = [block_init(rng, cfg, spec, dtype,
                           cross=cfg.is_encoder_decoder)
                for _ in range(n_reps)]
        stack[f"pos{j}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *reps)
    params["stack"] = stack
    params["final_norm"] = rmsnorm_init(d, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(rng, d, vocab, dtype)
    if cfg.modality is not None:
        params["mod_proj"] = dense_init(rng, d, d, dtype)
    if cfg.is_encoder_decoder:
        enc_spec = ("attn", "mlp")
        reps = [block_init(rng, cfg, enc_spec, dtype)
                for _ in range(cfg.num_encoder_layers)]
        params["encoder"] = {
            "stack": jax.tree.map(lambda *xs: jnp.stack(xs), *reps),
            "final_norm": rmsnorm_init(d, dtype),
        }
    return params


# ---------------------------------------------------------------- positions

def _positions(cfg, n_mod: int, L_text: int, batch: int):
    """Position ids for the [modality prefix | text] layout."""
    if cfg.mrope:
        grid = max(1, int(math.ceil(math.sqrt(max(n_mod, 1)))))
        if n_mod > 0:
            idx = jnp.arange(n_mod)
            ppos = jnp.stack([jnp.zeros_like(idx), idx // grid, idx % grid],
                             axis=-1)
        else:
            ppos = jnp.zeros((0, 3), jnp.int32)
        t = jnp.arange(L_text) + grid
        tpos = jnp.stack([t, t, t], axis=-1)
        pos = jnp.concatenate([ppos, tpos], axis=0).astype(jnp.int32)
        return jnp.broadcast_to(pos, (batch,) + pos.shape)
    pos = jnp.arange(n_mod + L_text, dtype=jnp.int32)
    return jnp.broadcast_to(pos, (batch, n_mod + L_text))


# ---------------------------------------------------------------- forward

def _run_encoder(params, cfg, frames):
    """Bidirectional encoder over modality frame embeddings."""
    x = frames @ params["mod_proj"]
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    enc_spec = ("attn", "mlp")

    def body(carry, rep_params):
        h, _, _ = block_forward(rep_params, cfg, enc_spec, carry, pos,
                                causal=False)
        return h, None

    x, _ = jax.lax.scan(body, x, params["encoder"]["stack"])
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def _maybe_shard_seq(cfg, h):
    """Megatron-style sequence sharding of the residual stream at block
    boundaries (perf lever; EXPERIMENTS.md §Perf): with remat, the stored
    per-layer activation shrinks by the model-axis size, at the cost of
    an all-gather before each block's attention."""
    if not cfg.shard_seq:
        return h
    from repro.sharding.context import get_mesh
    mesh = get_mesh()
    if mesh is None:
        return h
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = ([None] * h.ndim)
    axes[0] = ("pod", "data") if "pod" in mesh.axis_names else "data"
    axes[1] = "model"
    return jax.lax.with_sharding_constraint(
        h, NamedSharding(mesh, P(*axes)))


def _logits(params, cfg, x):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    return (x @ head).astype(jnp.float32)


def lm_apply(params, cfg, tokens, *, modality_embeds=None, remat: bool = True,
             collect_cache: bool = False, cache_capacity: int | None = None,
             logits_mode: str = "all", return_stats: bool = False):
    """Training / prefill forward.

    tokens: (B, L_text) int32. modality_embeds: (B, n_mod, d_model) for
    vlm/audio archs (the stub frontend's output). Returns
    (logits, aux_loss[, cache]), or with return_stats (no cache)
    (logits, aux_loss, stats): the MoE layers' routing counters
    (`models/moe.STATS`; {} for configs that count none). For vlm,
    logits cover the full [prefix|text] sequence; the caller slices
    text positions for loss. remat recomputes each scanned layer in the
    backward, keeping only attention's residuals (module docstring).
    """
    B, L_text = tokens.shape
    lead, period, _ = layer_groups(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    enc_out = None
    n_mod = 0
    if cfg.is_encoder_decoder:
        assert modality_embeds is not None
        enc_out = _run_encoder(params, cfg, modality_embeds)
    elif cfg.modality is not None:
        assert modality_embeds is not None
        n_mod = modality_embeds.shape[1]
        x = jnp.concatenate(
            [modality_embeds.astype(x.dtype) @ params["mod_proj"], x], axis=1)
    positions = _positions(cfg, n_mod, L_text, B)
    aux = jnp.zeros((), jnp.float32)
    stats = zero_stats(cfg)
    L_total = n_mod + L_text
    capacity = cache_capacity or L_total

    caches = {}
    for i, spec in enumerate(lead):
        if collect_cache:
            x, a, caches[f"lead_{i}"] = block_prefill(
                params[f"lead_{i}"], cfg, spec, x, positions, capacity,
                enc_out=enc_out)
        else:
            x, a, st = block_forward(params[f"lead_{i}"], cfg, spec, x,
                                     positions, enc_out=enc_out)
            stats = merge_stats(stats, st)
        aux = aux + a

    if collect_cache:
        def body(carry, rep_params):
            h, acc = carry
            rep_caches = {}
            for j, spec in enumerate(period):
                h, a, rep_caches[f"pos{j}"] = block_prefill(
                    rep_params[f"pos{j}"], cfg, spec, h, positions, capacity,
                    enc_out=enc_out)
                acc = acc + a
            return (h, acc), rep_caches

        (x, aux), stack_caches = jax.lax.scan(body, (x, aux),
                                              params["stack"])
        caches["stack"] = stack_caches
    else:
        def body(carry, rep_params):
            h, acc, st = carry
            for j, spec in enumerate(period):
                h, a, s = block_forward(rep_params[f"pos{j}"], cfg, spec, h,
                                        positions, enc_out=enc_out)
                acc = acc + a
                st = merge_stats(st, s)
            h = _maybe_shard_seq(cfg, h)
            return (h, acc, st), None

        if remat:   # keep attention's residuals, recompute the rest
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.save_only_these_names(
                    RESIDUAL_O, RESIDUAL_LSE))
        (x, aux, stats), _ = jax.lax.scan(body, (x, aux, stats),
                                          params["stack"])

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if logits_mode == "last":
        x = x[:, -1:]          # serving prefill: next-token logits only
    logits = _logits(params, cfg, x)
    if collect_cache:
        caches["length"] = jnp.asarray(L_total, jnp.int32)
        if enc_out is not None:
            caches["enc_out"] = enc_out
        return logits, aux, caches
    if return_stats:
        return logits, aux, stats
    return logits, aux


# ---------------------------------------------------------------- decode

def init_decode_cache(cfg, batch: int, capacity: int, dtype=None,
                      enc_out=None, *, full: bool = True):
    """Decode cache pytree sized for `capacity` cached tokens. With
    full=True the cache is marked as already holding `capacity` tokens
    (steady-state decode, as in the assigned decode shapes)."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    lead, period, n_reps = layer_groups(cfg)
    caches = {}
    for i, spec in enumerate(lead):
        caches[f"lead_{i}"] = block_init_cache(cfg, spec, batch, capacity,
                                               dtype)
    stack = {}
    for j, spec in enumerate(period):
        one = block_init_cache(cfg, spec, batch, capacity, dtype)
        stack[f"pos{j}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_reps,) + x.shape), one)
    caches["stack"] = stack
    caches["length"] = jnp.asarray(capacity if full else 0, jnp.int32)
    if enc_out is not None:
        caches["enc_out"] = enc_out
    return caches


def lm_decode_step(params, cfg, tokens, cache):
    """One-token decode. tokens: (B, 1) int32. Returns (logits, cache)."""
    lead, period, n_reps = layer_groups(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    length = cache["length"]
    enc_out = cache.get("enc_out")
    new_cache = {"length": length + 1}
    if enc_out is not None:
        new_cache["enc_out"] = enc_out

    for i, spec in enumerate(lead):
        x, new_cache[f"lead_{i}"] = block_decode(
            params[f"lead_{i}"], cfg, spec, x, cache[f"lead_{i}"], length,
            enc_out=enc_out)

    # The stacked cache rides the layer loop as a carry and each layer
    # writes its slice back with dynamic_update_slice, so a donated
    # cache is updated in place. (As scan input and output it would be
    # a second full cache beside the first.)
    def body(r, carry):
        h, stack = carry
        for j, spec in enumerate(period):
            layer = jax.tree.map(
                lambda c: jax.lax.dynamic_index_in_dim(c, r, keepdims=False),
                stack[f"pos{j}"])
            rep_params = jax.tree.map(
                lambda p: jax.lax.dynamic_index_in_dim(p, r, keepdims=False),
                params["stack"][f"pos{j}"])
            h, layer = block_decode(rep_params, cfg, spec, h, layer, length,
                                    enc_out=enc_out)
            stack = {**stack, f"pos{j}": jax.tree.map(
                lambda c, u: jax.lax.dynamic_update_index_in_dim(c, u, r, 0),
                stack[f"pos{j}"], layer)}
        return h, stack

    x, new_cache["stack"] = jax.lax.fori_loop(0, n_reps, body,
                                              (x, cache["stack"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), new_cache
