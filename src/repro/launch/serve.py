"""Personalized serving launcher: batched decode on the chips present
(or --reduced on CPU), plus the builders that wire an LM config into
`federated.serving.ServingEngine` (adaptation-on-demand, DESIGN.md §18).

Without --reduced the decode batch is each chip's share of the shape's
global batch, as on the production pod's 16-way data axis.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --shape decode_32k --steps 4
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
      --shape decode_32k --steps 4 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs, reduced_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import PRODUCTION_DATA, make_device_mesh
from repro.launch.steps import (input_specs, make_apply_fn, make_decode_step,
                                make_prefill_step, resolve_serving_config)
from repro.models import init_lm
from repro.sharding.rules import param_pspecs


def build_serving_fns(cfg):
    """(prefill, decode) entry points for `ServingEngine` — the same
    builders the dry-run lowers at production scale."""
    return make_prefill_step(cfg), make_decode_step(cfg)


def build_engine(cfg, phi=None, *, algo_name: str = "fomaml",
                 inner_lr: float = 0.05, inner_steps: int = 1,
                 adapt_batch: int = 4, cache_capacity: Optional[int] = 64,
                 adapt_impl: Optional[str] = None,
                 decode_impl: Optional[str] = None, seed: int = 0):
    """Wire an LM config into a `ServingEngine`: FedMeta algorithm over
    `lm_loss`, prefill/decode serve steps, bounded adaptation cache.
    `phi` defaults to a fresh init (tests/benches); production passes
    the meta-trained state. `decode_impl` pins the decode-attention
    kernel ("xla" | "pallas" | "pallas_interpret") for everything this
    engine traces."""
    from repro.core import make_algorithm
    from repro.core.losses import lm_loss
    from repro.federated.serving import AdaptationCache, ServingEngine
    from repro.kernels.decode_attention import ops as dec_ops

    loss_fn, eval_fn = lm_loss(make_apply_fn(cfg, remat=False))
    algo = make_algorithm(algo_name, loss_fn, eval_fn, inner_lr, inner_steps)
    if phi is None:
        phi = {"theta": init_lm(jax.random.PRNGKey(seed), cfg)}
        if algo_name.startswith("meta-sgd"):
            phi = algo.init_state(jax.random.PRNGKey(seed),
                                  lambda k: init_lm(k, cfg))
    prefill, decode = build_serving_fns(cfg)
    if decode_impl is not None:
        raw = decode

        def decode(params, cache, tokens):
            with dec_ops.use_impl(decode_impl):
                return raw(params, cache, tokens)

    return ServingEngine(algo, phi, adapt_batch=adapt_batch,
                         adapt_steps=inner_steps,
                         cache=AdaptationCache(cache_capacity),
                         prefill_fn=prefill, decode_fn=decode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config + small shape (CPU execution)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    if shape.kind != "decode":
        raise SystemExit("use train.py for train shapes")

    if args.reduced:
        cfg = reduced_config(cfg)
        shape = dataclasses.replace(shape, seq_len=128, global_batch=2)
        mesh = make_device_mesh(jax.devices()[:1])
    else:
        mesh = make_device_mesh()
        n_data = mesh.devices.shape[0]
        per_chip = max(1, shape.global_batch // PRODUCTION_DATA)
        print(f"cut: {shape.name} on {n_data} chip(s): batch "
              f"{per_chip * n_data} (of {shape.global_batch}; {per_chip} "
              f"per chip on the production data axis) x "
              f"{shape.seq_len}-token cache", flush=True)
        shape = dataclasses.replace(shape, global_batch=per_chip * n_data)

    spec = input_specs(cfg, shape, mesh)
    scfg = spec["serving_cfg"]
    decode = make_decode_step(scfg)
    nm = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    pspec = param_pspecs(
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), scfg)), mesh)
    step = jax.jit(decode,
                   in_shardings=(nm(pspec), nm(spec["pspec"]["cache"]),
                                 nm(spec["pspec"]["tokens"])),
                   out_shardings=(None, nm(spec["pspec"]["cache"])),
                   donate_argnums=(1,))

    params = jax.jit(lambda k: init_lm(k, scfg),
                     out_shardings=nm(pspec))(jax.random.PRNGKey(0))
    cache = jax.jit(
        lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             spec["batch"]["cache"]),
        out_shardings=nm(spec["pspec"]["cache"]))()
    cache["length"] = jnp.asarray(min(64, shape.seq_len), jnp.int32)
    tok = jnp.zeros((shape.global_batch, 1), jnp.int32)
    for it in range(args.steps):
        t0 = time.perf_counter()
        logits, cache = step(params, cache, tok)
        jax.block_until_ready(logits)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        print(f"decode step {it}: {time.perf_counter()-t0:.2f}s  "
              f"logits {logits.shape}", flush=True)


if __name__ == "__main__":
    main()
