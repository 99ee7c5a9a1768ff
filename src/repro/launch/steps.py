"""Entry-point builders for the production LM configs.

  train_step    FedMeta meta-training round over a task batch of clients
                (G client-groups over the pod axis x C clients scanned x
                S sequences data-parallel)
  prefill_step  (params, batch) -> (next-token logits, decode cache)
  decode_step   (params, cache, tokens) -> (logits, cache)

`input_specs` builds ShapeDtypeStruct stand-ins + PartitionSpecs for every
entry point — the dry-run lowers against these (no allocation).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, InputShape, ModelConfig
from repro.core.algorithms import make_algorithm
from repro.core.fedmeta import federated_meta_step
from repro.core.losses import lm_loss
from repro.kernels.meta_update import ops as mu_ops
from repro.kernels.ssd import ops as ssd_ops
from repro.models import init_lm, lm_apply, init_decode_cache, lm_decode_step
from repro.optim import Optimizer, adam
from repro.sharding.rules import (batch_axes, batch_pspec, cache_pspecs,
                                  param_pspecs, state_pspecs)

LONG_CONTEXT_WINDOW = 8192   # SWA window applied to dense archs @ long_500k


def resolve_serving_config(cfg: ModelConfig, shape: InputShape) -> ModelConfig:
    """long_500k requires sub-quadratic attention: dense/full-attention
    archs run their sliding-window variant (DESIGN.md §6)."""
    if (shape.name == "long_500k" and cfg.sliding_window is None
            and cfg.attention == "gqa" and any(k == "attn"
                                               for k in cfg.layer_pattern)):
        return dataclasses.replace(cfg, sliding_window=LONG_CONTEXT_WINDOW)
    return cfg


def make_apply_fn(cfg: ModelConfig, *, remat: bool = True):
    """apply(params, batch) -> (logits, aux); batch = tokens or dict.

    This is the forward of the LM loss, which training and adaptation
    differentiate. Attention takes the platform's path: the flash
    kernels have a first-order backward, and a step that differentiates
    twice (second-order MAML, Meta-SGD) runs XLA attention by the
    algorithm's ``dispatch.second_order`` scope. The SSD kernel has no
    backward, so the SSD scan is pinned to XLA here, whatever the
    platform (serving's prefill keeps the kernel). Where the MoE layers
    count their routing (DeepSeek gating), apply returns (logits, aux,
    stats) and the LM loss reports the counters."""

    def apply_fn(params, batch):
        with ssd_ops.use_impl("xla"):
            if isinstance(batch, dict):
                tokens, embeds = batch["tokens"], batch.get("embeds")
            else:
                tokens, embeds = batch, None
            logits, aux, stats = lm_apply(
                params, cfg, tokens, modality_embeds=embeds, remat=remat,
                return_stats=True)
            return (logits, aux, stats) if stats else (logits, aux)

    return apply_fn


def reduce_metrics(mets, axis: int = 0):
    """Per-client metrics -> the round's: means, except the MoE routing
    counters, which are summed (`moe_load_max`: the largest)."""
    def one(k, x):
        if k == "moe_load_max":
            return jnp.max(x, axis=axis)
        if k.startswith("moe_"):
            return jnp.sum(x, axis=axis)
        return jnp.mean(x, axis=axis)
    return {k: one(k, x) for k, x in mets.items()}


# ------------------------------------------------------------- train step

def make_train_step(cfg: ModelConfig, *, algo_name: str = "fomaml",
                    inner_lr: float = 0.01, outer_lr: float = 1e-4,
                    inner_steps: int = 1, remat: bool = True,
                    opt_state_dtype="float32"):
    """FedMeta meta-training step for an LM arch.

    state = {"phi": {...}, "opt": {...}}
    batch = {"support": leaf(G, C, S, ...), "query": ...} — G client groups
    (pod-parallel), C clients (scanned), S sequences (data-parallel).
    """
    loss_fn, eval_fn = lm_loss(make_apply_fn(cfg, remat=remat))
    algo = make_algorithm(algo_name, loss_fn, eval_fn, inner_lr, inner_steps)
    optimizer = adam(outer_lr, state_dtype=jnp.dtype(opt_state_dtype))

    def init_state(key):
        phi = algo.init_state(key, lambda k: init_lm(k, cfg))
        return {"phi": phi, "opt": optimizer.init(phi)}

    def train_step(state, batch):
        # the tree inner update stays on XLA: the Pallas path packs all
        # of θ into an f32 plane every inner step, which for
        # smollm-360m on a v5e costs 1.7 GB more temp (9.3 -> 11.0 GB)
        # and 8x the compile time (21 -> 173 s) for one elementwise pass
        # that XLA already fuses per leaf. Both figures come from
        # ahead-of-time compiles only; the two paths' step times on the
        # chip have not been compared
        with mu_ops.use_impl("xla"):
            return _train_step(state, batch)

    def _train_step(state, batch):
        def per_group(sup, qry):
            # scan over clients with a meta-gradient accumulator: only one
            # adapted θ_u is live at a time (DESIGN.md §4)
            def body(acc, sq):
                s, q = sq
                g, met = algo.client_grad(state["phi"], s, q)
                acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), acc, g)
                return acc, met

            acc0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                state["phi"] if algo_name.startswith("meta-sgd")
                                else {"theta": state["phi"]["theta"]})
            C = jax.tree.leaves(sup)[0].shape[0]
            meta_g, mets = jax.lax.scan(body, acc0, (sup, qry))
            meta_g = jax.tree.map(lambda x: x / C, meta_g)
            return meta_g, reduce_metrics(mets)

        meta_g, mets = jax.vmap(per_group)(batch["support"], batch["query"])
        meta_g = jax.tree.map(lambda x: jnp.mean(x, axis=0), meta_g)
        mets = reduce_metrics(mets)
        phi, opt = optimizer.update(state["phi"], meta_g, state["opt"])
        return {"phi": phi, "opt": opt}, mets

    return train_step, init_state, algo, optimizer


# ------------------------------------------------------------ serve steps

def make_prefill_step(cfg: ModelConfig):
    def prefill_step(params, batch):
        tokens = batch["tokens"] if isinstance(batch, dict) else batch
        embeds = batch.get("embeds") if isinstance(batch, dict) else None
        logits, aux, cache = lm_apply(params, cfg, tokens,
                                      modality_embeds=embeds, remat=False,
                                      collect_cache=True, logits_mode="last")
        return logits[:, 0], cache

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    def decode_step(params, cache, tokens):
        logits, new_cache = lm_decode_step(params, cfg, tokens, cache)
        return logits[:, 0], new_cache

    return decode_step


# ------------------------------------------------------------ input specs

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def train_batch_layout(cfg: ModelConfig, shape: InputShape, n_pods: int):
    """(G, C, S_support, S_query, L_text, n_mod)."""
    G = n_pods
    S = shape.seqs_per_client
    C = shape.global_batch // (G * S)
    assert C * G * S == shape.global_batch, (shape.name, G, S)
    n_mod = cfg.num_modality_tokens if cfg.modality else 0
    L_text = shape.seq_len - (n_mod if cfg.modality == "vision" else 0)
    return G, C, S // 2, S - S // 2, L_text, n_mod


def input_specs(cfg: ModelConfig, shape: InputShape, mesh, *,
                cache_seq_shard: bool = False) -> dict:
    """ShapeDtypeStructs + PartitionSpecs for the entry point of `shape`.

    Returns {"args": (...sds...), "pspecs": (...matching specs...)}.
    """
    n_pods = mesh.devices.shape[0] if "pod" in mesh.axis_names else 1
    act_dtype = jnp.dtype(cfg.dtype)

    if shape.kind == "train":
        G, C, S_sup, S_qry, L_text, n_mod = train_batch_layout(
            cfg, shape, n_pods)

        def part(S):
            leaf = {"tokens": _sds((G, C, S, L_text), jnp.int32)}
            spec = {"tokens": P("pod" if n_pods > 1 else None, None,
                                "data", None)}
            if cfg.modality:
                leaf["embeds"] = _sds((G, C, S, n_mod, cfg.d_model), act_dtype)
                spec["embeds"] = P("pod" if n_pods > 1 else None, None,
                                   "data", None, None)
            return leaf, spec

        sup, sup_spec = part(S_sup)
        qry, qry_spec = part(S_qry)
        return {"batch": {"support": sup, "query": qry},
                "pspec": {"support": sup_spec, "query": qry_spec}}

    B = shape.global_batch
    baxes = batch_axes(mesh)
    bsize = int(np.prod([dict(zip(mesh.axis_names,
                                  mesh.devices.shape))[a] for a in baxes]))
    b_ax = (baxes if len(baxes) > 1 else baxes[0]) if B % bsize == 0 else None

    if shape.kind == "prefill":
        n_mod = cfg.num_modality_tokens if cfg.modality else 0
        L_text = shape.seq_len - (n_mod if cfg.modality == "vision" else 0)
        batch = {"tokens": _sds((B, L_text), jnp.int32)}
        spec = {"tokens": P(b_ax, None)}
        if cfg.modality:
            batch["embeds"] = _sds((B, n_mod, cfg.d_model), act_dtype)
            spec["embeds"] = P(b_ax, None, None)
        return {"batch": batch, "pspec": spec}

    # decode: one token against a seq_len cache
    serving_cfg = resolve_serving_config(cfg, shape)

    def build_cache():
        enc_out = None
        if cfg.is_encoder_decoder:
            enc_out = jnp.zeros((B, cfg.num_modality_tokens, cfg.d_model),
                                act_dtype)
        return init_decode_cache(serving_cfg, B, shape.seq_len,
                                 dtype=act_dtype, enc_out=enc_out)

    cache = jax.eval_shape(build_cache)
    cache_spec = cache_pspecs(cache, mesh, seq_shard=cache_seq_shard)
    tokens = _sds((B, 1), jnp.int32)
    return {"batch": {"tokens": tokens, "cache": cache},
            "pspec": {"tokens": P(b_ax, None), "cache": cache_spec},
            "serving_cfg": serving_cfg}
