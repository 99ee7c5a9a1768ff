"""Loss / metric functions shared by FedMeta and the baselines."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_xent(logits, labels):
    """Mean cross entropy. logits: (..., C) f32; labels: (...) int."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))


def topk_accuracy(logits, labels, k: int):
    topk = jax.lax.top_k(logits, k)[1]                       # (..., k)
    hit = jnp.any(topk == labels[..., None], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))


def classification_loss(apply_fn, topk=()):
    """-> loss_fn(params, (x, y)) and eval_fn(params, (x, y))->(loss, metrics).

    ``topk`` adds ``top{k}`` accuracy metrics (paper §4.3 reports Top-1 and
    Top-4 on the production recommendation task). This builder also serves
    the *local-head* convention of that scenario: labels may be client-local
    ids (``data/synth_recommend.localize_clients``) over a small head
    instead of global service ids over the full catalogue — the loss/eval
    math is unchanged, only the label space (and therefore the model's
    output width, the θ-size asymmetry of DESIGN.md §13) differs.
    """

    def loss_fn(params, batch):
        x, y = batch
        return softmax_xent(apply_fn(params, x), y)

    def eval_fn(params, batch):
        x, y = batch
        logits = apply_fn(params, x)
        metrics = {"accuracy": accuracy(logits, y)}
        for k in topk:
            metrics[f"top{k}"] = topk_accuracy(logits, y, k)
        return softmax_xent(logits, y), metrics

    return loss_fn, eval_fn


def lm_loss(apply_fn):
    """Next-token LM loss over token batches.

    Batches are either a (B, L) token array or a dict with "tokens"
    (+ "embeds" for modality archs — consumed by apply_fn).
    apply_fn(params, batch) -> (logits (B, L', V), aux[, stats]) — aux
    (e.g. MoE load-balance loss) is added to the objective so the router
    trains in both FedMeta loops; stats (the MoE routing counters), where
    given, join the eval metrics. L' may include a modality prefix; loss
    aligns to the last L text positions."""

    def _tokens(batch):
        return batch["tokens"] if isinstance(batch, dict) else batch

    def loss_fn(params, batch):
        tokens = _tokens(batch)
        logits, aux = apply_fn(params, batch)[:2]
        logits = logits[:, -tokens.shape[1]:]
        return softmax_xent(logits[:, :-1], tokens[:, 1:]) + aux

    def eval_fn(params, batch):
        tokens = _tokens(batch)
        logits, aux, *stats = apply_fn(params, batch)
        logits = logits[:, -tokens.shape[1]:]
        loss = softmax_xent(logits[:, :-1], tokens[:, 1:])
        return loss + aux, {"accuracy": accuracy(logits[:, :-1], tokens[:, 1:]),
                            "nll": loss, **(stats[0] if stats else {})}

    return loss_fn, eval_fn


def lm_pair_loss(apply_fn):
    """`lm_loss` behind the federated (x, y) batch convention.

    The experiment plane's task pipeline (`data/federated.py`) hands every
    loss a ``(x, y)`` pair; for LM personalization tasks x IS the (B, L)
    token batch and the target is the shifted sequence itself, so y is
    ignored. This is the adapter that lets per-client dialect corpora
    (`data/lm_tasks.make_lm_clients`) run through `run_comparison`
    unchanged — FedMeta adapts on support sequences, scores next-token
    accuracy on query sequences.
    """
    base_loss, base_eval = lm_loss(apply_fn)

    def loss_fn(params, batch):
        x, _ = batch
        return base_loss(params, x)

    def eval_fn(params, batch):
        x, _ = batch
        return base_eval(params, x)

    return loss_fn, eval_fn
