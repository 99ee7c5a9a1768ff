"""Plain reference for deepseek-v2-lite meta-training, one chip's share.

DeepSeek-V2-Lite (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
arXiv:2405.04434) is a decoder of multi-head latent attention (MLA)
layers; the first layer's feed-forward is a dense SwiGLU, every later
one a mixture of experts. Per layer, with h = rmsnorm(x):

  attention  q = Wq·h split per head into q_nope (128) and q_pe (64);
             c = rmsnorm(Wdkv·h) (the 512-wide latent), k_pe = Wkpe·h
             (one 64-wide rope part shared by every head);
             k_nope = Wuk·c, v = Wuv·c per head; q_pe and k_pe rotated
             by YaRN rope; softmax((q_nope·k_nope + q_pe·k_pe) · s)
             causal, s = 192^-1/2 · mscale(40, 0.707)^2, mscale(f, m) =
             0.1·m·ln f + 1; x += Wo · (p · v)
  dense FFN  x += Wdown·(silu(Wgate·h) * Wup·h), width 10944
  MoE FFN    router: softmax over all 64 experts of the float32 product
             of h with the router weight; the 6 largest probabilities
             and their experts (greedy, not renormalized, times the
             routed scaling factor 1); each expert e a SwiGLU of width
             1408 weighted by its probability where it was picked; plus
             2 shared experts as one SwiGLU of width 2816, for every
             token; the sequence balance loss alpha · Σ_e f_e P_e (f_e:
             the sequence's picks of e over 6·L/64, P_e: its mean
             probability), averaged over sequences, joins the loss

then the final rmsnorm and the untied head. YaRN rope (DeepSeek-V2's
rotary): inverse frequencies theta^(-2i/64) where dimension i turns more
than beta_fast = 32 times over the original 4096 positions, those over
40 where it turns fewer than beta_slow = 1 times, a linear ramp between;
cos and sin times mscale(40, 0.707) / mscale(40, 0.707) = 1.

One chip's share: this chip holds routed experts 0-7 of the 64. Each
token's output takes the held experts' part only (a pick of another
expert adds nothing); the router and the picks are over all 64. The
vocabulary is an eighth, 12800 rows. Written as a plain loop over the 8
held experts, each run on every token and weighted by a 0/1 mask of its
picks (no sort, no gather).

Departures from the release: rotary pairs are the two halves of the rope
part (the release pairs interleaved dimensions: a fixed relabelling of
random weights); attention is computed in blocks of queries so that it
fits beside what the chip still holds.

The round is FedMeta's first-order MAML with Adam, as in the smollm-360m
reference beside this file: each client one SGD step on its support
sequences, θ_u kept in the parameters' dtype, its query gradient at θ_u;
the clients' mean in float32; one Adam step.

Written in plain `jax.numpy` from that description. It imports nothing
of the program. Parameters are stored in the configuration's dtype
(bfloat16); each matrix product takes its operands in that dtype and
accumulates in float32 (the router's product is the float32 product of
those operands); everything else is float32 under
`jax.default_matmul_precision("highest")`. The `fp8` policy rounds each
product's operands to scaled float8 (e4m3 forward, e5m2 backward): the
control that a lower precision than the configuration states must fail.
A float32 configuration (the CPU tests' tiny one) takes the `f32`
policy, which rounds nothing, and `bf16` as its control.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "R": cfg["kv_lora_rank"],
            "F": cfg["intermediate_size"], "Fe": cfg["moe_intermediate_size"],
            "S": cfg["n_shared_experts"], "Eh": cfg["n_routed_experts"],
            "E": cfg["router_experts"], "e0": cfg["first_held_expert"],
            "K": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "n": cfg["num_hidden_layers"],
            "dense": cfg["first_k_dense_replace"]}


# ------------------------------------------------------------- weights

def _attn_shapes(k: dict, n: int | None) -> dict:
    lead = () if n is None else (n,)
    d, H, R = k["d"], k["H"], k["R"]
    qk = k["nope"] + k["rope"]
    return {
        "attn_norm": (lead + (d,), None),
        "wq": (lead + (d, H * qk), d),
        "w_dkv": (lead + (d, R), d),
        "kv_norm": (lead + (R,), None),
        "w_kpe": (lead + (d, k["rope"]), d),
        "w_uk": (lead + (R, H * k["nope"]), R),
        "w_uv": (lead + (R, H * k["vd"]), R),
        "wo": (lead + (H * k["vd"], d), H * k["vd"]),
        "mlp_norm": (lead + (d,), None),
    }


def param_shapes(cfg: dict) -> dict:
    """name -> (shape, fan_in | None for a norm scale | 0.02 the
    embedding's std)."""
    k = dims(cfg)
    d, F, Fe, S, Eh, E, V = (k[x] for x in ("d", "F", "Fe", "S", "Eh", "E",
                                            "V"))
    n_moe = k["n"] - k["dense"]
    if k["dense"] != 1:
        raise ValueError("the reference follows one leading dense layer")
    shapes = {"embed": ((V, d), 0.02), "final_norm": ((d,), None),
              "lm_head": ((d, V), d)}
    for name, spec in _attn_shapes(k, None).items():
        shapes[f"dense.{name}"] = spec
    shapes.update({"dense.w_gate": ((d, F), d), "dense.w_up": ((d, F), d),
                   "dense.w_down": ((F, d), F)})
    for name, spec in _attn_shapes(k, n_moe).items():
        shapes[f"moe.{name}"] = spec
    shapes.update({
        "moe.router": ((n_moe, d, E), d),
        "moe.e_gate": ((n_moe, Eh, d, Fe), d),
        "moe.e_up": ((n_moe, Eh, d, Fe), d),
        "moe.e_down": ((n_moe, Eh, Fe, d), Fe),
        "moe.s_gate": ((n_moe, d, Fe * S), d),
        "moe.s_up": ((n_moe, d, Fe * S), d),
        "moe.s_down": ((n_moe, Fe * S, d), Fe * S),
    })
    return shapes


def init_params(key_data, cfg: dict) -> dict:
    """Random weights from two uint32 words of key data, in the
    configuration's dtype: normal with std 1/sqrt(fan_in), the
    embedding with std 0.02, norm scales near 1."""
    dtype = jnp.dtype(cfg["dtype"])
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    shapes = param_shapes(cfg)
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


def _as_f32(x):
    return x.astype(F32)


def make_mm(policy: str):
    """mm(spec, a, b): an einsum whose operands are rounded to the
    policy's precision, forward and backward, accumulating in float32.
    "f32" rounds nothing (the policy of a float32 configuration)."""
    if policy == "f32":
        q_fwd = q_bwd = _as_f32
    elif policy == "bf16":
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


# ---------------------------------------------------------------- YaRN

def mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies of the 64-wide rope part."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    factor = float(rs["factor"])
    orig = rs["original_max_position_embeddings"]
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (base ** exps)
    inter = 1.0 / (factor * base ** exps)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extra_share = 1.0 - ramp
    return (inter * (1 - extra_share) + extra * extra_share).astype(
        np.float32)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = mscale(float(rs["factor"]), rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope(x, cfg: dict):
    """x: (B, L, heads, 64); rotates the two halves of the rope part."""
    rs = cfg["rope_scaling"]
    L, dim = x.shape[1], x.shape[-1]
    half = dim // 2
    ang = np.arange(L, dtype=np.float32)[:, None] * yarn_inv_freq(cfg)[None]
    m = mscale(float(rs["factor"]), rs["mscale"]) / mscale(
        float(rs["factor"]), rs["mscale_all_dim"])
    cos = jnp.asarray(np.cos(ang) * m)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * m)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


# ------------------------------------------------------------- forward

def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def attention(x, p, cfg, mm):
    k = dims(cfg)
    B, L, _ = x.shape
    H, nope, vd = k["H"], k["nope"], k["vd"]
    q = mm("bld,de->ble", x, p["wq"]).reshape(B, L, H, nope + k["rope"])
    q_nope, q_pe = q[..., :nope], rope(q[..., nope:], cfg)
    c = rmsnorm(mm("bld,dr->blr", x, p["w_dkv"]), p["kv_norm"],
                cfg["rms_norm_eps"])
    k_pe = rope(mm("bld,de->ble", x, p["w_kpe"])[:, :, None, :], cfg)
    k_nope = mm("blr,re->ble", c, p["w_uk"]).reshape(B, L, H, nope)
    v = mm("blr,re->ble", c, p["w_uv"]).reshape(B, L, H, vd)
    scale = softmax_scale(cfg)
    outs = []
    for q0 in range(0, L, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, L)
        s = (mm("bqhd,bkhd->bhqk", q_nope[:, q0:q1], k_nope[:, :q1])
             + mm("bqhd,bkd->bhqk", q_pe[:, q0:q1], k_pe[:, :q1, 0]))
        causal = np.arange(q0, q1)[:, None] >= np.arange(q1)[None, :]
        s = jnp.where(causal[None, None], s * scale, -jnp.inf)
        outs.append(mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                       v[:, :q1]))
    o = jnp.concatenate(outs, axis=1).reshape(B, L, H * vd)
    return mm("ble,ed->bld", o, p["wo"])


def swiglu(h, w_gate, w_up, w_down, mm):
    a = mm("bld,df->blf", h, w_gate)
    b = mm("bld,df->blf", h, w_up)
    return mm("blf,fd->bld", jax.nn.silu(a) * b, w_down)


def gating(h, router, cfg, mm):
    """-> (probs (B, L, E), weights (B, L, K), experts (B, L, K))."""
    logits = mm("bld,de->ble", h, router)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and cfg["num_experts_per_tok"] > 1:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    else:
        weights = weights * cfg["routed_scaling_factor"]
    return probs, weights, experts


def balance_loss(probs, experts, cfg):
    k = dims(cfg)
    B, L, E = probs.shape
    picks = jnp.sum(jax.nn.one_hot(experts, E, dtype=F32), axis=(1, 2))
    f = picks / (L * k["K"] / E)
    return cfg["aux_loss_alpha"] * jnp.mean(
        jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


def moe(h, p, cfg, mm):
    """-> (y, balance loss): the held experts' part and the shared."""
    k = dims(cfg)
    probs, weights, experts = gating(h, p["router"], cfg, mm)
    y = swiglu(h, p["s_gate"], p["s_up"], p["s_down"], mm)
    for e in range(k["Eh"]):
        mask = (experts == k["e0"] + e).astype(F32)          # (B, L, K)
        w = jnp.sum(weights * mask, axis=-1)[..., None]      # (B, L, 1)
        y = y + w * swiglu(h, p["e_gate"][e], p["e_up"][e],
                           p["e_down"][e], mm)
    return y, balance_loss(probs, experts, cfg)


def layer(x, p, cfg, mm, kind: str):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rmsnorm(x, p["attn_norm"], eps), p, cfg, mm)
    h = rmsnorm(x, p["mlp_norm"], eps)
    if kind == "dense":
        return x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mm), 0.0
    y, aux = moe(h, p, cfg, mm)
    return x + y, aux


def lm_loss(p, tokens, cfg, mm):
    """Mean next-token cross entropy of (B, L) tokens plus the balance
    losses; p in float32."""
    x = jnp.take(p["embed"], tokens, axis=0)
    x, aux = jax.checkpoint(
        lambda h, lp: layer(h, lp, cfg, mm, "dense"))(x, p["dense"])

    def step(carry, lp):
        h, a = carry
        h, la = layer(h, lp, cfg, mm, "moe")
        return (h, a + la), None

    (x, aux), _ = jax.lax.scan(jax.checkpoint(step), (x, aux), p["moe"])
    x = rmsnorm(x, p["final_norm"], cfg["rms_norm_eps"])
    logits = mm("bld,dv->blv", x[:, :-1], p["lm_head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(ll) + aux


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
            with jax.default_matmul_precision("highest"):
                _, g = grad(_f32(theta), tokens)
            return jax.tree.map(
                lambda t, gg: (t.astype(F32) - alpha * gg).astype(t.dtype),
                theta, g)

        @functools.partial(jax.jit, donate_argnums=(2,))
        def query(theta_u, tokens, acc):
            with jax.default_matmul_precision("highest"):
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

def held_pairs_per_token(cfg: dict) -> float:
    """Expected (token, expert) pairs a token sends to the held experts:
    k picks over E experts, Eh of them here."""
    k = dims(cfg)
    return k["K"] * k["Eh"] / k["E"]


def matmul_params(cfg: dict) -> float:
    """Weights that enter a matrix product once per token: attention's
    projections, the dense FFN, per MoE layer the router, the shared
    experts and the held experts at the expected pairs a token sends
    them, and the head; not the embedding lookup."""
    k = dims(cfg)
    d, H, R = k["d"], k["H"], k["R"]
    attn = (d * H * (k["nope"] + k["rope"]) + d * R + d * k["rope"]
            + R * H * k["nope"] + R * H * k["vd"] + H * k["vd"] * d)
    dense = 3 * d * k["F"]
    moe = (d * k["E"] + 3 * d * k["Fe"] * k["S"]
           + held_pairs_per_token(cfg) * 3 * d * k["Fe"])
    n_moe = k["n"] - k["dense"]
    return k["n"] * attn + k["dense"] * dense + n_moe * moe + d * k["V"]


def train_flops_per_sequence(cfg: dict, seq_len: int) -> float:
    """Forward + backward model FLOPs of one sequence: 6 per matmul
    weight per token, and causal attention's two products (QK^T over
    192 dims, PV over 128) over half the L x L square, times 3 for the
    backward; no recompute."""
    k = dims(cfg)
    dense = 6 * matmul_params(cfg) * seq_len
    attn = 3 * k["n"] * seq_len * seq_len * k["H"] * (
        k["nope"] + k["rope"] + k["vd"])
    return float(dense + attn)


def fomaml_flops_per_round(cfg: dict, clients: int, support_seqs: int,
                           query_seqs: int, seq_len: int) -> float:
    """A client's support pass and query pass are each one forward and
    one backward."""
    return clients * (support_seqs + query_seqs) * \
        train_flops_per_sequence(cfg, seq_len)


# ------------------------------------------- the expert kernels' work

def expert_call_cost(cfg: dict, pairs: float) -> tuple[float, float]:
    """(FLOPs, least bytes) of one expert_gmm or expert_tgmm call of one
    MoE layer in one pass when `pairs` (token, expert) pairs reach the
    held experts. Each of the pass's calls (3 forward, 3 recomputed, 3
    input gradients, 3 weight gradients) is a (pairs x d) by (d x Fe)
    product or its like: 2·pairs·d·Fe FLOPs; its least bytes read the
    held experts' weights (or write their gradient) once and its two
    row operands (pairs x d and pairs x Fe) once, in bfloat16."""
    k = dims(cfg)
    d, Fe, Eh = k["d"], k["Fe"], k["Eh"]
    flops = 2.0 * pairs * d * Fe
    least_bytes = 2.0 * (Eh * d * Fe + pairs * (d + Fe))
    return flops, least_bytes
