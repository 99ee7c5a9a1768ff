"""Plain reference for the femnist-cnn paper rounds.

The FedMeta paper's FEMNIST model (arXiv:1802.07876, section 4.1; also
LEAF's reference CNN): 28x28 grey images; a 5x5 convolution to 32
channels, ReLU, 2x2 max-pooling; a 5x5 convolution to 64 channels, ReLU,
2x2 max-pooling (both convolutions 'same'-padded, stride 1); a dense
layer of 2048 units with ReLU; a dense layer to 62 classes; softmax cross
entropy.

A round (paper Algorithm 1, first-order MAML): the server draws the
round's writers and each writer's support and query sets (the draw is
the trainer's seeded sampling, written out below); each writer takes one
SGD step on its support set, θ_u = θ - α ∇L_S(θ), and returns the
gradient of its query loss at θ_u; the server weights the writers by
their local sample counts, sums, and takes one Adam step.

Written in plain `jax.numpy` (and `lax.conv_general_dilated`) from that
description, in float32 with every product at the precision the
configuration states ("highest": float32). Imports nothing of the
program. The control computes the same at "high", the next precision
below: each product, forward and backward, as three bfloat16 passes
(hi·hi + hi·lo + lo·hi of each operand split into a bfloat16 high part
and a bfloat16 remainder), written out so that it means the same on
every backend.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LEAVES = ("c1", "c2", "fc1", "out")


def shapes(cfg: dict) -> dict:
    k, c0 = cfg["kernel_size"], cfg["in_channels"]
    c1, c2 = cfg["conv1_channels"], cfg["conv2_channels"]
    feat = (cfg["image_size"] // (cfg["pool"] ** 2)) ** 2 * c2
    return {"c1": ((k, k, c0, c1), k * k * c0),
            "c2": ((k, k, c1, c2), k * k * c1),
            "fc1": ((feat, cfg["hidden"]), feat),
            "out": ((cfg["hidden"], cfg["num_classes"]), cfg["hidden"])}


def init_params(key_data, cfg: dict) -> dict:
    """Weights normal with std 1/sqrt(fan_in), biases 0.01 normal."""
    key = jax.random.wrap_key_data(jnp.asarray(key_data, jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    sh = shapes(cfg)
    for name, k in zip(LEAVES, jax.random.split(key, len(LEAVES))):
        (w_shape, fan) = sh[name]
        kw, kb = jax.random.split(k)
        out[name] = {
            "w": jax.random.normal(kw, w_shape, F32) / math.sqrt(fan),
            "b": 0.01 * jax.random.normal(kb, (w_shape[-1],), F32)}
    return out


def flatten(tree: dict) -> dict:
    return {f"{a}.{b}": v for a, sub in tree.items() for b, v in sub.items()}


# ------------------------------------------------------------ products

HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(F32)
    return hi, (x - hi).astype(jnp.bfloat16).astype(F32)


def _three_pass(f, a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def three_pass(f):
    """The bilinear product `f` at "high": three bfloat16 passes forward,
    and for each operand's gradient in the backward pass."""
    @jax.custom_vjp
    def op(a, b):
        return _three_pass(f, a, b)

    def fwd(a, b):
        return op(a, b), (a, b)

    def bwd(res, g):
        a, b = res

        def t_a(gg, bb):
            return jax.vjp(lambda x: f(x, bb), a)[1](gg)[0]

        def t_b(gg, aa):
            return jax.vjp(lambda y: f(aa, y), b)[1](gg)[0]
        return _three_pass(t_a, g, b), _three_pass(t_b, g, a)

    op.defvjp(fwd, bwd)
    return op


PRODUCTS = {"highest": (_conv, _dot),
            "high": (three_pass(_conv), three_pass(_dot))}


# ------------------------------------------------------------- forward

def maxpool2(x):
    """2x2 max-pooling; where a window holds equal maxima, the gradient
    goes to the first of them (as `lax.reduce_window`'s does, and as
    frameworks route max-pool gradients), not split among them."""
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def logits(p, x, precision="highest"):
    conv, dot = PRODUCTS[precision]
    x = x[..., None]
    x = maxpool2(jax.nn.relu(conv(x, p["c1"]["w"]) + p["c1"]["b"]))
    x = maxpool2(jax.nn.relu(conv(x, p["c2"]["w"]) + p["c2"]["b"]))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dot(x, p["fc1"]["w"]) + p["fc1"]["b"])
    return dot(x, p["out"]["w"]) + p["out"]["b"]


def xent(p, x, y, precision="highest"):
    logp = jax.nn.log_softmax(logits(p, x, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


# ------------------------------------------------------------ sampling

def _resample(x, y, size, rng):
    n = len(y)
    idx = rng.choice(n, size=size, replace=n < size)
    return x[idx], y[idx]


def sample_round(writers, traffic: dict, rng: np.random.RandomState):
    """One round's tasks, drawn as the trainer draws them: writers
    uniformly; each writer's data split into disjoint support and query
    sets by a random permutation (support share `support_frac`), each
    resampled to its fixed size; weights by local sample count."""
    m = traffic["clients_per_round"]
    picks = rng.choice(len(writers), size=m, replace=len(writers) < m)
    sx, sy, qx, qy, w = [], [], [], [], []
    for ci in picks:
        x, y = writers[ci]
        n = len(y)
        perm = rng.permutation(n)
        n_sup = max(1, min(n - 1, int(round(traffic["support_frac"] * n))))
        a, b = _resample(x[perm[:n_sup]], y[perm[:n_sup]],
                         traffic["support_size"], rng)
        c, d = _resample(x[perm[n_sup:]], y[perm[n_sup:]],
                         traffic["query_size"], rng)
        sx.append(a), sy.append(b), qx.append(c), qy.append(d)
        w.append(n)
    w = np.asarray(w, np.float32)
    return (np.stack(sx), np.stack(sy), np.stack(qx), np.stack(qy),
            w / w.sum())


# --------------------------------------------------------------- round

class Reference:
    """FedMeta FOMAML rounds with Adam, all writers of a round at once."""

    def __init__(self, cfg: dict, precision: str | None = None):
        if cfg["algorithm"] != "fomaml" or cfg.get("inner_steps", 1) != 1:
            raise ValueError("the reference follows one-step FOMAML")
        self.cfg = cfg
        loss = functools.partial(
            xent, precision=precision or cfg["matmul_precision"])
        alpha = cfg["inner_lr"]
        b1, b2 = cfg["adam_b1"], cfg["adam_b2"]
        lr, eps = cfg["outer_lr"], cfg["adam_eps"]

        def client(theta, sx, sy, qx, qy):
            g = jax.grad(loss)(theta, sx, sy)
            theta_u = jax.tree.map(lambda t, gg: t - alpha * gg, theta, g)
            return jax.value_and_grad(loss)(theta_u, qx, qy)

        def round_(theta, m, v, t, sx, sy, qx, qy, w):
            losses, grads = jax.vmap(client, in_axes=(None, 0, 0, 0, 0))(
                theta, sx, sy, qx, qy)
            g = jax.tree.map(
                lambda x: jnp.tensordot(w, x, axes=1, precision=HIGHEST),
                grads)
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                             v, g)
            mhat, vhat = 1.0 / (1 - b1 ** t), 1.0 / (1 - b2 ** t)
            theta = jax.tree.map(
                lambda p, m_, v_: p - lr * (m_ * mhat) / (
                    jnp.sqrt(v_ * vhat) + eps), theta, m, v)
            return theta, m, v, jnp.dot(w, losses, precision=HIGHEST), g

        self._round = jax.jit(round_)

    def run(self, theta0: dict, writers, traffic: dict, task_seed: int,
            rounds: int, *, keep=None) -> dict:
        """`rounds` rounds from `theta0` (host arrays) with the trainer's
        task draw seeded by `task_seed`. `keep(tasks)` may cut a round's
        tasks (a fault). -> {"losses", "grad_norms", "delta_norms"}."""
        rng = np.random.RandomState(task_seed)
        theta = jax.device_put(theta0)
        m = jax.tree.map(jnp.zeros_like, theta)
        v = jax.tree.map(jnp.zeros_like, theta)
        losses, grad_norms = [], None
        for t in range(1, rounds + 1):
            tasks = sample_round(writers, traffic, rng)
            if keep is not None:
                tasks = keep(tasks)
            theta, m, v, loss, g = self._round(
                theta, m, v, jnp.float32(t), *map(jnp.asarray, tasks))
            losses.append(float(loss))
            if t == 1:
                grad_norms = leaf_norms(g)
        delta = jax.tree.map(lambda a, b: a - b, theta,
                             jax.device_put(theta0))
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": leaf_norms(delta)}


def leaf_norms(tree) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in flatten(tree).items()}


# --------------------------------------------------- FLOPs and bytes

def forward_flops_per_image(cfg: dict) -> float:
    """2 per multiply-add of the two convolutions and two dense layers."""
    s, k = cfg["image_size"], cfg["kernel_size"]
    c0, c1, c2 = cfg["in_channels"], cfg["conv1_channels"], \
        cfg["conv2_channels"]
    conv1 = s * s * c1 * k * k * c0
    s2 = s // cfg["pool"]
    conv2 = s2 * s2 * c2 * k * k * c1
    feat = (s2 // cfg["pool"]) ** 2 * c2
    dense = feat * cfg["hidden"] + cfg["hidden"] * cfg["num_classes"]
    return 2.0 * (conv1 + conv2 + dense)


def fomaml_flops_per_round(cfg: dict, traffic: dict) -> float:
    """Each writer's support and query passes: forward and backward (3x
    the forward) over its images."""
    images = traffic["clients_per_round"] * (traffic["support_size"]
                                             + traffic["query_size"])
    return 3.0 * images * forward_flops_per_image(cfg)


def num_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) + s[-1] for s, _ in shapes(cfg).values())


def kernel_bytes(cfg: dict, traffic: dict) -> dict:
    """Least HBM bytes of each of the round's three elementwise passes
    over the (clients, params) f32 plane: the inner update reads θ and g
    and writes θ_u; the weighted aggregate reads every writer's row and
    writes one; Adam reads φ, g, m, v and writes φ, m, v."""
    n, c = num_params(cfg), traffic["clients_per_round"]
    return {"inner_update": 3 * c * n * 4, "aggregate": (c + 1) * n * 4,
            "adam": 7 * n * 4}
