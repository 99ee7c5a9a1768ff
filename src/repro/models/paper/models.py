"""The paper's experiment models (Appendix A.1), in pure JAX.

- FEMNIST CNN: two 5x5 conv layers (32, 64 ch) each + 2x2 maxpool, then a
  dense layer (2048 in the paper; configurable) and a 62-way softmax.
- Shakespeare: stacked 2-layer char-LSTM, 256 hidden, 8-d embedding.
- Sent140: 2-layer LSTM, 100 hidden, learned embeddings (the paper uses
  frozen 300-d GloVe; no pretrained vectors offline — noted in DESIGN.md).
- Recommendation: LR and one-hidden-layer NN (64 units), paper §4.3.

Each factory returns a `Model(init, apply)`; apply(params, x) -> logits.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import Rng, dense_init, embed_init


class Model(NamedTuple):
    init: Callable          # (key) -> params
    apply: Callable         # (params, x) -> logits
    name: str


# ------------------------------------------------------------------ CNN

def femnist_cnn(num_classes: int = 62, image_size: int = 28,
                hidden: int = 256, dtype=jnp.float32) -> Model:
    """Paper's CNN (hidden=2048 in the paper and in the femnist-cnn
    bench config; the default is reduced for CPU-scale runs)."""

    def conv(x, w, b):
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + b

    def maxpool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    feat_hw = image_size // 4

    def init(key):
        rng = Rng(key)
        def conv_w(kh, kw, cin, cout):
            fan = kh * kw * cin
            return (jax.random.truncated_normal(
                rng.next(), -2, 2, (kh, kw, cin, cout), jnp.float32)
                / np.sqrt(fan)).astype(dtype)
        return {
            "c1": {"w": conv_w(5, 5, 1, 32), "b": jnp.zeros((32,), dtype)},
            "c2": {"w": conv_w(5, 5, 32, 64), "b": jnp.zeros((64,), dtype)},
            "fc1": {"w": dense_init(rng, feat_hw * feat_hw * 64, hidden, dtype),
                    "b": jnp.zeros((hidden,), dtype)},
            "out": {"w": dense_init(rng, hidden, num_classes, dtype),
                    "b": jnp.zeros((num_classes,), dtype)},
        }

    def apply(params, x):
        if x.ndim == 3:
            x = x[..., None]                      # (B, H, W, 1)
        x = maxpool(jax.nn.relu(conv(x, params["c1"]["w"], params["c1"]["b"])))
        x = maxpool(jax.nn.relu(conv(x, params["c2"]["w"], params["c2"]["b"])))
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
        return x @ params["out"]["w"] + params["out"]["b"]

    return Model(init, apply, "femnist_cnn")


# ----------------------------------------------------------------- LSTM

def _lstm_layer_init(rng: Rng, d_in: int, hidden: int, dtype):
    return {"w": dense_init(rng, d_in + hidden, 4 * hidden, dtype),
            "b": jnp.zeros((4 * hidden,), dtype)}


def _lstm_layer(params, xs, hidden: int):
    """xs: (B, L, d_in) -> (B, L, hidden)."""
    B = xs.shape[0]

    def step(carry, x_t):
        h, c = carry
        z = jnp.concatenate([x_t, h], axis=-1) @ params["w"] + params["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    h0 = jnp.zeros((B, hidden), xs.dtype)
    (_, _), hs = jax.lax.scan(step, (h0, h0), jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1)


def _stacked_lstm(vocab: int, embed_dim: int, hidden: int, num_layers: int,
                  num_classes: int, dtype, name: str) -> Model:
    def init(key):
        rng = Rng(key)
        p = {"embed": embed_init(rng, vocab, embed_dim, dtype)}
        d_in = embed_dim
        for l in range(num_layers):
            p[f"lstm{l}"] = _lstm_layer_init(rng, d_in, hidden, dtype)
            d_in = hidden
        p["out"] = {"w": dense_init(rng, hidden, num_classes, dtype),
                    "b": jnp.zeros((num_classes,), dtype)}
        return p

    def apply(params, x):
        h = jnp.take(params["embed"], x, axis=0)   # (B, L, e)
        for l in range(num_layers):
            h = _lstm_layer(params[f"lstm{l}"], h, hidden)
        return h[:, -1] @ params["out"]["w"] + params["out"]["b"]

    return Model(init, apply, name)


def char_lstm(vocab: int = 70, num_classes: int | None = None,
              hidden: int = 256, embed_dim: int = 8,
              dtype=jnp.float32) -> Model:
    return _stacked_lstm(vocab, embed_dim, hidden, 2,
                         num_classes or vocab, dtype, "char_lstm")


def sent_lstm(vocab: int = 2000, hidden: int = 100, embed_dim: int = 64,
              dtype=jnp.float32) -> Model:
    return _stacked_lstm(vocab, embed_dim, hidden, 2, 2, dtype, "sent_lstm")


# -------------------------------------------------------------- rec task

def rec_lr(feat_dim: int, num_classes: int, dtype=jnp.float32) -> Model:
    def init(key):
        rng = Rng(key)
        return {"w": dense_init(rng, feat_dim, num_classes, dtype),
                "b": jnp.zeros((num_classes,), dtype)}

    def apply(params, x):
        return x @ params["w"] + params["b"]

    return Model(init, apply, "rec_lr")


def rec_nn(feat_dim: int, num_classes: int, hidden: int = 64,
           dtype=jnp.float32) -> Model:
    def init(key):
        rng = Rng(key)
        return {"w1": dense_init(rng, feat_dim, hidden, dtype),
                "b1": jnp.zeros((hidden,), dtype),
                "w2": dense_init(rng, hidden, num_classes, dtype),
                "b2": jnp.zeros((num_classes,), dtype)}

    def apply(params, x):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]

    return Model(init, apply, "rec_nn")
