"""Shared model primitives: init helpers, norms, MLP variants, rotary
embeddings (incl. M-RoPE). Functional style: params are nested dicts.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


class Rng:
    """Splitting helper so init code doesn't thread keys manually."""

    def __init__(self, key):
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self._key = key

    def next(self):
        self._key, sub = jax.random.split(self._key)
        return sub


def dense_init(rng: Rng, d_in: int, d_out: int, dtype, scale: float | None = None):
    """Truncated-normal fan-in init (LeCun-ish), stored in `dtype`."""
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    w = jax.random.truncated_normal(rng.next(), -2.0, 2.0, (d_in, d_out),
                                    jnp.float32) * scale
    return w.astype(dtype)


def embed_init(rng: Rng, vocab: int, d: int, dtype):
    w = jax.random.normal(rng.next(), (vocab, d), jnp.float32) * 0.02
    return w.astype(dtype)


# ---------------------------------------------------------------- norms

def rmsnorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- MLPs

def mlp_init(rng: Rng, d: int, d_ff: int, act: str, dtype):
    p = {"w_down": dense_init(rng, d_ff, d, dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(rng, d, d_ff, dtype)
        p["w_up"] = dense_init(rng, d, d_ff, dtype)
    else:
        p["w_up"] = dense_init(rng, d, d_ff, dtype)
    return p


def mlp_apply(params, x, act: str):
    if act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(x @ params["w_up"]))
    elif act == "gelu":
        h = jax.nn.gelu(x @ params["w_up"])
    else:
        raise ValueError(f"unknown act {act}")
    return h @ params["w_down"]


# ---------------------------------------------------------------- rotary

def rope_frequencies(head_dim: int, theta: float):
    """Inverse frequencies for half the head dim."""
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor 0.1·mscale·ln(factor) + 1 (1 unscaled)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float):
    """YaRN inverse frequencies for half of `dim`, as DeepSeek-V2's
    rotary builds them: the unscaled frequencies where a dimension turns
    more than `beta_fast` times over the original context, those divided
    by `factor` where it turns fewer than `beta_slow` times, and a linear
    ramp between the two."""
    extra = rope_frequencies(dim, theta)
    inter = extra / np.float32(factor)

    def correction_dim(rotations):
        return (dim * math.log(original_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp            # 1: the unscaled frequency
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x, positions, theta: float, inv_freq=None, mscale=1.0):
    """Standard RoPE. x: (..., L, H, hd); positions: (..., L) int32.
    `inv_freq` (hd/2,) replaces the plain frequencies of `theta` (YaRN);
    `mscale` multiplies cos and sin."""
    hd = x.shape[-1]
    inv = jnp.asarray(rope_frequencies(hd, theta) if inv_freq is None
                      else inv_freq)                        # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * inv    # (..., L, hd/2)
    cos = jnp.cos(ang)[..., None, :]                        # (..., L, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections):
    """M-RoPE (Qwen2-VL): rotary dims split into (t, h, w) sections.

    x: (..., L, H, hd); positions3: (..., L, 3) int32; sections sum to hd/2.
    """
    hd = x.shape[-1]
    half = hd // 2
    assert sum(sections) == half, (sections, half)
    inv = jnp.asarray(rope_frequencies(hd, theta))          # (half,)
    # pick which position component drives each rotary dim
    comp = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.asarray(comp)[None, :].astype(jnp.int32) *
        jnp.ones(positions3.shape[:-1] + (half,), jnp.int32),
        axis=-1)                                            # (..., L, half)
    ang = pos * inv
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def text_mrope_positions(positions):
    """Text tokens use identical (t,h,w) components."""
    return jnp.stack([positions, positions, positions], axis=-1)
