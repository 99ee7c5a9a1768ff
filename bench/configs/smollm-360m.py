"""Plain reference for smollm-360m meta-training.

SmolLM-360M (https://huggingface.co/HuggingFaceTB/SmolLM-360M) is a
Llama-style decoder: token embedding, then per layer
  x += Wo · attn(rope(Wq·rmsnorm(x)), rope(Wk·rmsnorm(x)), Wv·rmsnorm(x))
  x += Wdown · (silu(Wgate·rmsnorm(x)) * Wup·rmsnorm(x))
with causal grouped-query attention (15 query heads over 5 key/value
heads, head size 64), rotary positions on halves of each head
(HF `rotate_half`), RMSNorm with a learned scale, and logits through
the tied embedding.

The round is FedMeta's first-order MAML (paper Algorithm 1): each
client takes one SGD step on its support sequences from θ,
θ_u = θ - α ∇L_S(θ), kept in the parameters' dtype, and contributes the
gradient of its query loss at θ_u; the server averages the clients'
gradients in float32 and takes one Adam step on θ.

Written in plain `jax.numpy` from that description. It imports nothing
of the program. Parameters are stored in the configuration's dtype
(bfloat16). Every matrix product takes its operands in that dtype and
accumulates in float32; everything else (norms, rotary, softmax, loss,
gradients) is float32. The `fp8` policy rounds each product's operands
to scaled float8 instead (e4m3 forward, e5m2 backward): the control
that a lower precision than the configuration states must fail.

To fit one chip beside nothing else, a round runs client by client:
one call adapts θ on a client's support set, one adds its query
gradient to the running sum, one takes the Adam step; each layer is
recomputed in the backward pass (`jax.checkpoint`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "Kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // H,
            "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"]}


# ------------------------------------------------------------- weights

def init_params(key_data, cfg: dict) -> dict:
    """Random weights from two uint32 words of key data, in the
    configuration's dtype: normal with std 1/sqrt(fan_in), the
    embedding with std 0.02, norm scales near 1."""
    k = dims(cfg)
    d, H, Kv, hd, F, V, n = (k[x] for x in ("d", "H", "Kv", "hd", "F", "V",
                                            "n"))
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    shapes = {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), None),
        "layers.attn_norm": ((n, d), None),
        "layers.wq": ((n, d, H * hd), d),
        "layers.wk": ((n, d, Kv * hd), d),
        "layers.wv": ((n, d, Kv * hd), d),
        "layers.wo": ((n, H * hd, d), H * hd),
        "layers.mlp_norm": ((n, d), None),
        "layers.w_gate": ((n, d, F), d),
        "layers.w_up": ((n, d, F), d),
        "layers.w_down": ((n, F, d), F),
    }
    keys = jax.random.split(key, len(shapes))
    flat = {}
    for kk, (name, (shape, fan)) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(kk, shape, F32)
        if fan is None:                       # a norm's scale
            w = 1.0 + 0.05 * z
        elif isinstance(fan, float):          # the embedding's std
            w = fan * z
        else:
            w = z / math.sqrt(fan)
        flat[name] = w.astype(dtype)
    return unflatten(flat)


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for key, val in tree.items():
        name = f"{prefix}{key}"
        if isinstance(val, dict):
            out.update(flatten(val, name + "."))
        else:
            out[name] = val
    return out


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for name, val in flat.items():
        node = tree
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = val
    return tree


# ------------------------------------------------------------ products

def _scaled(dtype, fmax):
    def q(x):
        s = jax.lax.stop_gradient(jnp.max(jnp.abs(x))).astype(F32) / fmax
        s = jnp.maximum(s, 1e-30)
        return (x / s).astype(dtype).astype(F32) * s
    return q


def _bf16(x):
    return x.astype(jnp.bfloat16)


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def make_mm(policy: str):
    """mm(spec, a, b): an einsum whose operands are rounded to the
    policy's precision, forward and backward, accumulating in float32."""
    if policy == "bf16":
        q_fwd = q_bwd = _bf16
    elif policy == "fp8":
        q_fwd = _scaled(jnp.float8_e4m3fn, 448.0)
        q_bwd = _scaled(jnp.float8_e5m2, 57344.0)
    else:
        raise ValueError(f"unknown precision policy {policy!r}")

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm(spec, a, b):
        return _einsum(spec, q_fwd(a), q_fwd(b))

    def fwd(spec, a, b):
        qa, qb = q_fwd(a), q_fwd(b)
        return _einsum(spec, qa, qb), (qa, qb)

    def bwd(spec, res, g):
        qa, qb = res
        _, vjp = jax.vjp(functools.partial(_einsum, spec),
                         qa.astype(F32), qb.astype(F32))
        da, db = vjp(q_bwd(g).astype(F32))
        return da, db

    mm.defvjp(fwd, bwd)
    return mm


# ------------------------------------------------------------- forward

def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, theta):
    """x: (B, L, heads, hd); rotates the two halves of each head."""
    L, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = np.arange(L, dtype=np.float32)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def attention(q, k, v, mm):
    """Causal grouped-query attention. q: (B, L, H, hd); k, v:
    (B, L, Kv, hd); query head h reads key/value head h // (H / Kv)."""
    B, L, H, hd = q.shape
    group = H // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = np.tril(np.ones((L, L), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v)


def decoder_layer(x, p, cfg, mm):
    k = dims(cfg)
    B, L, _ = x.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h = rmsnorm(x, p["attn_norm"], eps)
    q = mm("bld,de->ble", h, p["wq"]).reshape(B, L, k["H"], k["hd"])
    kk = mm("bld,de->ble", h, p["wk"]).reshape(B, L, k["Kv"], k["hd"])
    vv = mm("bld,de->ble", h, p["wv"]).reshape(B, L, k["Kv"], k["hd"])
    o = attention(rope(q, theta), rope(kk, theta), vv, mm)
    x = x + mm("ble,ed->bld", o.reshape(B, L, k["H"] * k["hd"]), p["wo"])
    h = rmsnorm(x, p["mlp_norm"], eps)
    a = mm("bld,df->blf", h, p["w_gate"])
    b = mm("bld,df->blf", h, p["w_up"])
    return x + mm("blf,fd->bld", jax.nn.silu(a) * b, p["w_down"])


def lm_loss(p, tokens, cfg, mm):
    """Mean next-token cross entropy of (B, L) tokens; p in float32."""
    x = jnp.take(p["embed"], tokens, axis=0)
    layer = jax.checkpoint(
        lambda h, lp: (decoder_layer(h, lp, cfg, mm), None))
    x, _ = jax.lax.scan(layer, x, p["layers"])
    x = rmsnorm(x, p["final_norm"], cfg["rms_norm_eps"])
    logits = mm("bld,vd->blv", x[:, :-1], p["embed"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(ll)


# --------------------------------------------------------------- round

def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


class Reference:
    """FedMeta FOMAML rounds with Adam, client by client."""

    def __init__(self, cfg: dict, policy: str = "bf16"):
        if cfg["algorithm"] != "fomaml" or cfg.get("inner_steps", 1) != 1:
            raise ValueError("the reference follows one-step FOMAML")
        self.cfg = cfg
        mm = make_mm(policy)
        alpha = cfg["inner_lr"]
        loss = functools.partial(lm_loss, cfg=cfg, mm=mm)
        grad = jax.value_and_grad(loss)

        @jax.jit
        def adapt(theta, tokens):
            _, g = grad(_f32(theta), tokens)
            return jax.tree.map(
                lambda t, gg: (t.astype(F32) - alpha * gg).astype(t.dtype),
                theta, g)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def query(theta_u, tokens, acc):
            value, g = grad(_f32(theta_u), tokens)
            return value, jax.tree.map(jnp.add, acc, g)

        b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
        lr, eps = cfg["outer_lr"], cfg["adam_eps"]

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def adam(theta, m, v, acc, clients, t):
            g = jax.tree.map(lambda a: a / clients, acc)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                             v, g)
            mhat, vhat = 1.0 / (1 - b1 ** t), 1.0 / (1 - b2 ** t)
            theta = jax.tree.map(
                lambda p, m_, v_: (p.astype(F32) - lr * (m_ * mhat) / (
                    jnp.sqrt(v_ * vhat) + eps)).astype(p.dtype),
                theta, m, v)
            return theta, m, v

        self._adapt, self._query, self._adam = adapt, query, adam

    def run(self, theta0: dict, batches: list[dict], steps: int) -> dict:
        """Follows `steps` rounds from the weights `theta0` (host arrays,
        as `init_params` makes them) on `batches` (host arrays, as the
        traffic generator makes them) -> {"losses": [mean query loss of
        each round], "grad_norms": {leaf: |mean gradient| of round 1},
        "delta_norms": {leaf: |θ_steps - θ_0|}}."""
        theta = jax.device_put(theta0)
        zeros = lambda: jax.tree.map(  # noqa: E731
            lambda x: jnp.zeros(x.shape, F32), theta)
        m, v = zeros(), zeros()
        losses, grad_norms = [], None
        for t in range(1, steps + 1):
            sup = batches[t - 1]["support"]
            qry = batches[t - 1]["query"]
            clients = sup.shape[0]
            acc, total = zeros(), 0.0
            for c in range(clients):
                theta_u = self._adapt(theta, jnp.asarray(sup[c]))
                value, acc = self._query(theta_u, jnp.asarray(qry[c]), acc)
                total += float(value)
                del theta_u
            losses.append(total / clients)
            if t == 1:
                grad_norms = leaf_norms(
                    jax.tree.map(lambda a: a / clients, acc))
            theta, m, v = self._adam(theta, m, v, acc, float(clients),
                                     float(t))
        delta_norms = leaf_norms(diff_f32(theta, jax.device_put(theta0)))
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": delta_norms}


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32)))), tree)


@jax.jit
def diff_f32(a, b):
    return jax.tree.map(lambda x, y: x.astype(F32) - y.astype(F32), a, b)


def leaf_norms(tree) -> dict:
    return {k: float(v) for k, v in flatten(_norms(tree)).items()}


# --------------------------------------------------------------- FLOPs

def matmul_params(cfg: dict) -> int:
    """Weights that enter a matrix product once per token: the layers'
    projections and the (tied) output head; not the embedding lookup."""
    k = dims(cfg)
    d, H, Kv, hd, F = k["d"], k["H"], k["Kv"], k["hd"], k["F"]
    per_layer = d * H * hd + 2 * d * Kv * hd + H * hd * d + 3 * d * F
    return k["n"] * per_layer + k["V"] * d


def train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs of one sequence: 6 per matmul
    weight per token, and causal attention's two products (QK^T, PV) over
    half the L x L square, times 3 for the backward; no recompute."""
    k = dims(cfg)
    dense = 6 * matmul_params(cfg) * seq_len
    attn = 6 * k["n"] * seq_len * seq_len * k["H"] * k["hd"]
    return float(dense + attn)


def fomaml_flops_per_round(cfg: dict, clients: int, support_seqs: int,
                           query_seqs: int, seq_len: int) -> float:
    """A client's support pass and query pass are each one forward and
    one backward."""
    return clients * (support_seqs + query_seqs) * \
        train_flops_per_sequence(cfg, seq_len)
