"""Attention modules: GQA (opt. bias / sliding window / M-RoPE), MLA
(DeepSeek-V2 latent attention with compressed KV cache), cross-attention
for the encoder-decoder, plus one-token decode paths.

Cache layouts (per layer):
  GQA:  {"k": (B, C, Kv, hd), "v": (B, C, Kv, hd)}  C = cache capacity
        (ring buffer when sliding window is active: C == window)
  MLA:  {"c": (B, C, R), "kpe": (B, C, rope_dim)}   — compressed latents
Both carry "length": () int32 — number of valid tokens already cached —
and the ring write position is length % C.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.attention import ops as attn_ops
from repro.models.layers import (Rng, apply_mrope, apply_rope, dense_init,
                                 rmsnorm, rmsnorm_init, text_mrope_positions,
                                 yarn_frequencies, yarn_mscale)


# ================================================================= GQA

def gqa_init(rng: Rng, cfg, dtype, *, cross: bool = False):
    d, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(rng, d, H * hd, dtype),
        "wk": dense_init(rng, d, Kv * hd, dtype),
        "wv": dense_init(rng, d, Kv * hd, dtype),
        "wo": dense_init(rng, H * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Kv * hd,), dtype)
        p["bv"] = jnp.zeros((Kv * hd,), dtype)
    return p


def _qkv(params, cfg, x, kv_input=None):
    B, L, _ = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kv_in = x if kv_input is None else kv_input
    Lk = kv_in.shape[1]
    q = x @ params["wq"]
    k = kv_in @ params["wk"]
    v = kv_in @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return (q.reshape(B, L, H, hd), k.reshape(B, Lk, Kv, hd),
            v.reshape(B, Lk, Kv, hd))


def _rope_qk(cfg, q, k, q_positions, k_positions):
    if cfg.mrope:
        qp = (q_positions if q_positions.shape[-1:] == (3,)
              else text_mrope_positions(q_positions))
        kp = (k_positions if k_positions.shape[-1:] == (3,)
              else text_mrope_positions(k_positions))
        q = apply_mrope(q, qp, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, kp, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, q_positions, cfg.rope_theta)
        k = apply_rope(k, k_positions, cfg.rope_theta)
    return q, k


def gqa_forward(params, cfg, x, positions, *, causal: bool = True,
                window=None, return_kv: bool = False):
    """Training / prefill self-attention. x: (B, L, d)."""
    B, L, _ = x.shape
    q, k, v = _qkv(params, cfg, x)
    q, k = _rope_qk(cfg, q, k, positions, positions)
    o = attn_ops.flash_attention(q, k, v, causal=causal, window=window)
    y = o.reshape(B, L, cfg.num_heads * cfg.head_dim) @ params["wo"]
    return (y, (k, v)) if return_kv else y


def cross_attn_forward(params, cfg, x, enc_out):
    """Decoder->encoder cross attention (no rope, no causal mask)."""
    B, L, _ = x.shape
    q, k, v = _qkv(params, cfg, x, kv_input=enc_out)
    o = attn_ops.flash_attention(q, k, v, causal=False)
    return o.reshape(B, L, cfg.num_heads * cfg.head_dim) @ params["wo"]


def gqa_init_cache(cfg, batch: int, capacity: int, dtype):
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, capacity, Kv, hd), dtype),
        "v": jnp.zeros((batch, capacity, Kv, hd), dtype),
    }


def gqa_decode(params, cfg, x, cache, length, *, window=None):
    """One-token decode. x: (B, 1, d); length: () valid tokens in cache.

    The new token's position is `length`; it is written into the ring slot
    length % C. Attention runs over the cache with positional masking
    handled via kv_length (cache is position-coherent because either
    C >= seq (full) or C == window (ring stores exactly the live window)).
    """
    B = x.shape[0]
    C = cache["k"].shape[1]
    q, k, v = _qkv(params, cfg, x)
    pos = jnp.full((B, 1), length, jnp.int32)
    q, k = _rope_qk(cfg, q, k, pos, pos)
    slot = (length % C).astype(jnp.int32)
    new_k = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    new_v = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    valid = jnp.minimum(length + 1, C)
    # Ring semantics: every valid slot is within the window by
    # construction, so decode attends to all valid slots uniformly.
    # Routed through the flash-decode kernel dispatcher (GQA-packed,
    # single cache pass on TPU; pure-jnp oracle on CPU/dry-run).
    from repro.kernels.decode_attention import ops as dec_ops
    o = dec_ops.decode_attention(q[:, 0], new_k, new_v, valid)[:, None]
    y = o.reshape(B, 1, cfg.num_heads * cfg.head_dim) @ params["wo"]
    return y, {"k": new_k, "v": new_v}


# ================================================================= MLA

def mla_init(rng: Rng, cfg, dtype):
    d, H = cfg.d_model, cfg.num_heads
    nope, rope_d, R = cfg.head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    p = {}
    if cfg.q_lora_rank > 0:
        p["w_dq"] = dense_init(rng, d, cfg.q_lora_rank, dtype)
        p["q_norm"] = rmsnorm_init(cfg.q_lora_rank, dtype)
        p["w_uq"] = dense_init(rng, cfg.q_lora_rank, H * (nope + rope_d), dtype)
    else:
        p["wq"] = dense_init(rng, d, H * (nope + rope_d), dtype)
    p["w_dkv"] = dense_init(rng, d, R, dtype)
    p["kv_norm"] = rmsnorm_init(R, dtype)
    p["w_kpe"] = dense_init(rng, d, rope_d, dtype)
    p["w_uk"] = dense_init(rng, R, H * nope, dtype)
    p["w_uv"] = dense_init(rng, R, H * nope, dtype)
    p["wo"] = dense_init(rng, H * nope, d, dtype)
    return p


def _mla_rope(cfg, x, positions):
    """Rotary positions on MLA's decoupled rope part; under YaRN its
    frequencies, and cos/sin times mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)."""
    f = cfg.yarn_factor
    if not f:
        return apply_rope(x, positions, cfg.rope_theta)
    inv = yarn_frequencies(x.shape[-1], cfg.rope_theta, f,
                           cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                           cfg.yarn_beta_slow)
    m = yarn_mscale(f, cfg.yarn_mscale) / yarn_mscale(
        f, cfg.yarn_mscale_all_dim)
    return apply_rope(x, positions, cfg.rope_theta, inv_freq=inv, mscale=m)


def mla_softmax_scale(cfg) -> float:
    """(nope + rope_d)^-1/2, times mscale(factor, mscale_all_dim)^2 under
    YaRN (DeepSeek-V2's attention)."""
    scale = 1.0 / np.sqrt(cfg.head_dim + cfg.rope_head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return float(scale)


def _mla_q(params, cfg, x):
    B, L, _ = x.shape
    H, nope, rope_d = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim
    if cfg.q_lora_rank > 0:
        q = rmsnorm(params["q_norm"], x @ params["w_dq"],
                    cfg.norm_eps) @ params["w_uq"]
    else:
        q = x @ params["wq"]
    q = q.reshape(B, L, H, nope + rope_d)
    return q[..., :nope], q[..., nope:]


def _mla_latents(params, cfg, x, positions):
    c = rmsnorm(params["kv_norm"], x @ params["w_dkv"],
                cfg.norm_eps)                                # (B, L, R)
    kpe = x @ params["w_kpe"]                                # (B, L, rope_d)
    kpe = _mla_rope(cfg, kpe[:, :, None, :], positions)[:, :, 0, :]
    return c, kpe


def mla_forward(params, cfg, x, positions, *, causal: bool = True,
                return_latents: bool = False):
    """Training / prefill MLA: materialize per-head k,v from latents."""
    B, L, _ = x.shape
    H, nope, rope_d = cfg.num_heads, cfg.head_dim, cfg.rope_head_dim
    q_nope, q_pe = _mla_q(params, cfg, x)
    q_pe = _mla_rope(cfg, q_pe, positions)
    c, kpe = _mla_latents(params, cfg, x, positions)
    k_nope = (c @ params["w_uk"]).reshape(B, L, H, nope)
    v = (c @ params["w_uv"]).reshape(B, L, H, nope)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kpe[:, :, None, :],
                                                  (B, L, H, rope_d))], axis=-1)
    o = attn_ops.flash_attention(q, k, v, causal=causal,
                                 scale=mla_softmax_scale(cfg))
    y = o.reshape(B, L, H * nope) @ params["wo"]
    return (y, (c, kpe)) if return_latents else y


def mla_init_cache(cfg, batch: int, capacity: int, dtype):
    return {
        "c": jnp.zeros((batch, capacity, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, capacity, cfg.rope_head_dim), dtype),
    }


def mla_decode(params, cfg, x, cache, length):
    """Absorbed one-token MLA decode: attention runs directly over the
    compressed latent cache (never materializes per-head K/V) —
    scores = (W_uk^T q_nope)·c + q_pe·k_pe, out = W_uv^T-projected attn·c.
    This is the TPU adaptation of DeepSeek-V2's weight-absorption trick.
    """
    B = x.shape[0]
    H, nope, rope_d, R = (cfg.num_heads, cfg.head_dim, cfg.rope_head_dim,
                          cfg.kv_lora_rank)
    C = cache["c"].shape[1]
    pos = jnp.full((B, 1), length, jnp.int32)
    q_nope, q_pe = _mla_q(params, cfg, x)                   # (B,1,H,·)
    q_pe = _mla_rope(cfg, q_pe, pos)
    c_new, kpe_new = _mla_latents(params, cfg, x, pos)
    slot = (length % C).astype(jnp.int32)
    c = jax.lax.dynamic_update_slice(cache["c"], c_new.astype(cache["c"].dtype),
                                     (0, slot, 0))
    kpe = jax.lax.dynamic_update_slice(cache["kpe"],
                                       kpe_new.astype(cache["kpe"].dtype),
                                       (0, slot, 0))
    valid = jnp.minimum(length + 1, C)
    # absorb W_uk into q: q_lat (B,H,R)
    w_uk = params["w_uk"].reshape(R, H, nope)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s = jnp.einsum("bhr,bjr->bhj", q_lat, c.astype(jnp.float32))
    s = s + jnp.einsum("bhd,bjd->bhj", q_pe[:, 0].astype(jnp.float32),
                       kpe.astype(jnp.float32))
    s = s * mla_softmax_scale(cfg)
    mask = jnp.arange(C)[None, None, :] < valid
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhj,bjr->bhr", p, c.astype(jnp.float32))  # (B,H,R)
    w_uv = params["w_uv"].reshape(R, H, nope)
    o = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    y = o.reshape(B, 1, H * nope).astype(x.dtype) @ params["wo"]
    return y, {"c": c, "kpe": kpe}
