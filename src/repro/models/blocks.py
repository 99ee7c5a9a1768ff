"""Decoder/encoder blocks assembled from mixers (attention / mamba / MoE).

A *layer spec* is (kind, ffn) with kind in {"attn", "mamba"} and ffn in
{"none", "mlp", "moe"}; the LM groups layers with identical specs into
scanned stacks (see lm.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import Rng, mlp_apply, mlp_init, rmsnorm, rmsnorm_init


def layer_spec(cfg, i: int):
    kind = cfg.layer_pattern[i % len(cfg.layer_pattern)]
    if cfg.d_ff == 0:
        ffn = "none"
    elif cfg.num_experts > 0 and cfg.is_moe_layer(i):
        ffn = "moe"
    else:
        ffn = "mlp"
    return (kind, ffn)


def block_init(rng: Rng, cfg, spec, dtype, *, cross: bool = False):
    kind, ffn = spec
    p = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if kind == "attn":
        if cfg.attention == "mla":
            p["mixer"] = attn.mla_init(rng, cfg, dtype)
        else:
            p["mixer"] = attn.gqa_init(rng, cfg, dtype)
    elif kind == "mamba":
        p["mixer"] = ssm.mamba_init(rng, cfg, dtype)
    else:
        raise ValueError(kind)
    if cross:
        p["norm_cross"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn.gqa_init(rng, cfg, dtype, cross=True)
    if ffn != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        if ffn == "moe":
            p["ffn"] = moe_mod.moe_init(rng, cfg, dtype)
        else:
            p["ffn"] = mlp_init(rng, cfg.d_model, cfg.mlp_d_ff, cfg.mlp_act,
                                dtype)
    return p


def block_forward(params, cfg, spec, x, positions, *, causal: bool = True,
                  enc_out=None):
    """Full-sequence forward. Returns (y, aux_loss, stats): stats are
    the MoE layer's routing counters ({} where it counts none)."""
    kind, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    stats = {}
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        if cfg.attention == "mla":
            y = attn.mla_forward(params["mixer"], cfg, h, positions,
                                 causal=causal)
        else:
            y = attn.gqa_forward(params["mixer"], cfg, h, positions,
                                 causal=causal, window=cfg.sliding_window)
    else:
        y = ssm.mamba_forward(params["mixer"], cfg, h)
    x = x + y
    if "cross" in params:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(params["cross"], cfg, h, enc_out)
    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux, stats = _moe(params["ffn"], cfg, h)
        else:
            y = mlp_apply(params["ffn"], h, cfg.mlp_act)
        x = x + y
    return x, aux, stats


def _moe(params, cfg, h):
    """Dispatch to the configured MoE implementation (perf lever).
    -> (y, aux_loss, routing counters)."""
    if cfg.moe_impl == "ep":
        from repro.sharding.context import get_mesh
        mesh = get_mesh()
        if mesh is not None:
            from repro.sharding.ep_moe import ep_moe_apply
            return (ep_moe_apply(params, cfg, h, mesh),
                    jnp.zeros((), jnp.float32), {})
    if cfg.router_scoring == "softmax":
        return moe_mod.held_moe_apply(params, cfg, h)
    y, aux = moe_mod.moe_apply(params, cfg, h)
    return y, aux, {}


def _ring_place(full, capacity: int):
    """Place the last min(L, capacity) of (B, L, ...) into a (B, capacity,
    ...) ring buffer at slots (j % capacity) — decode-coherent."""
    B, L = full.shape[:2]
    m = min(L, capacity)
    base = L - m
    slots = (base + jnp.arange(m)) % capacity
    buf = jnp.zeros((B, capacity) + full.shape[2:], full.dtype)
    return buf.at[:, slots].set(full[:, base:])


def block_prefill(params, cfg, spec, x, positions, capacity: int, *,
                  enc_out=None):
    """Forward that also emits a decode-ready cache. Returns (y, aux, cache)."""
    kind, ffn = spec
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        if cfg.attention == "mla":
            y, (c, kpe) = attn.mla_forward(params["mixer"], cfg, h, positions,
                                           return_latents=True)
            cache = {"c": _ring_place(c, capacity),
                     "kpe": _ring_place(kpe, capacity)}
        else:
            cap = (min(capacity, cfg.sliding_window)
                   if cfg.sliding_window else capacity)
            y, (k, v) = attn.gqa_forward(params["mixer"], cfg, h, positions,
                                         window=cfg.sliding_window,
                                         return_kv=True)
            cache = {"k": _ring_place(k, cap), "v": _ring_place(v, cap)}
    else:
        y, cache = ssm.mamba_forward(params["mixer"], cfg, h,
                                     return_cache=True)
    x = x + y
    if "cross" in params:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(params["cross"], cfg, h, enc_out)
    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, aux, _ = _moe(params["ffn"], cfg, h)
        else:
            y = mlp_apply(params["ffn"], h, cfg.mlp_act)
        x = x + y
    return x, aux, cache


def block_init_cache(cfg, spec, batch: int, capacity: int, dtype):
    kind, _ = spec
    if kind == "attn":
        if cfg.attention == "mla":
            return attn.mla_init_cache(cfg, batch, capacity, dtype)
        cap = min(capacity, cfg.sliding_window) if cfg.sliding_window else capacity
        return attn.gqa_init_cache(cfg, batch, cap, dtype)
    return ssm.mamba_init_cache(cfg, batch, dtype)


def block_decode(params, cfg, spec, x, cache, length, *, enc_out=None):
    """One-token decode. x: (B, 1, d). Returns (y, new_cache)."""
    kind, ffn = spec
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    if kind == "attn":
        if cfg.attention == "mla":
            y, cache = attn.mla_decode(params["mixer"], cfg, h, cache, length)
        else:
            y, cache = attn.gqa_decode(params["mixer"], cfg, h, cache, length,
                                       window=cfg.sliding_window)
    else:
        y, cache = ssm.mamba_decode(params["mixer"], cfg, h, cache)
    x = x + y
    if "cross" in params:
        h = rmsnorm(params["norm_cross"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(params["cross"], cfg, h, enc_out)
    if ffn != "none":
        h = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            y, _, _ = _moe(params["ffn"], cfg, h)
        else:
            y = mlp_apply(params["ffn"], h, cfg.mlp_act)
        x = x + y
    return x, cache
