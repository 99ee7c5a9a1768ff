"""Training launcher: FedMeta meta-training of an LM config.

Runs on the chips present (`jax.devices()`, a (data, model) = (n, 1)
mesh) at the architecture's published widths, on each chip's share of
the train shape: the shapes are sized for the production pod's 16-way
data axis, so every chip of the data axis takes 1/16 of each client's
sequences. On a CPU use --reduced (reduced config + small shape) to run
the same code path end to end.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --shape train_4k --algo fomaml --steps 3
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --shape train_4k --steps 20 --reduced
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import save_server_state
from repro.configs import (INPUT_SHAPES, InputShape, ModelConfig, get_config,
                           list_archs, reduced_config)
from repro.data.lm_tasks import make_lm_task_batch
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import PRODUCTION_DATA, make_device_mesh
from repro.launch.steps import input_specs, make_train_step, train_batch_layout
from repro.models.moe import STATS as ROUTING
from repro.sharding.rules import param_pspecs, state_pspecs
from repro.utils import trace  # registers the compile counter

ROUTE_SPAN = "fedmeta.moe.route"


def record_routing(metrics) -> dict | None:
    """A round's MoE routing counters (`moe_pairs_held`, `moe_load_max`,
    `moe_dropped`; DESIGN.md §19) read from the step's metrics and left
    in the trace as the stats of a `fedmeta.moe.route` span; None where
    the model counts none."""
    if ROUTING[0] not in metrics:
        return None
    counts = {k: int(metrics[k]) for k in ROUTING}
    with trace.span(ROUTE_SPAN, **counts):
        pass
    return counts


def per_chip_shape(shape: InputShape, n_data: int) -> InputShape:
    """`shape` cut to what `n_data` chips of a data axis hold: each
    client keeps seqs_per_client / 16 sequences per chip (the share one
    chip of the production data axis takes)."""
    per_chip = shape.seqs_per_client // PRODUCTION_DATA
    if per_chip < 2:
        raise ValueError(f"{shape.name}: {shape.seqs_per_client} sequences "
                         f"per client leave no support+query pair per "
                         f"chip of a {PRODUCTION_DATA}-way data axis")
    spc = per_chip * n_data
    return dataclasses.replace(shape, seqs_per_client=spc,
                               global_batch=shape.clients_per_round * spc)


def build_train(cfg: ModelConfig, shape: InputShape, mesh, *,
                algo: str = "fomaml", inner_lr: float = 0.01,
                outer_lr: float = 1e-4):
    """-> (step, init, make_batch): the jitted meta-train step sharded
    over `mesh` (state donated), `init(key)` -> its initial state, and
    `make_batch(it)`, the seeded task batch of step `it`. Nothing runs
    until `init` is called, so the step also lowers for described
    devices (`jax.eval_shape(init, key)` gives the sharded state)."""
    train_step, init_state, _, _ = make_train_step(
        cfg, algo_name=algo, inner_lr=inner_lr, outer_lr=outer_lr)
    spec = input_specs(cfg, shape, mesh)
    state_sds = jax.eval_shape(init_state, jax.random.PRNGKey(0))
    pspec = param_pspecs(state_sds["phi"]["theta"], mesh)
    sspec = state_pspecs(state_sds, pspec, mesh)

    def nm(t):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                            is_leaf=lambda x: isinstance(x, P))

    batch_sh = nm(spec["pspec"])
    step = jax.jit(train_step, in_shardings=(nm(sspec), batch_sh),
                   out_shardings=(nm(sspec), None), donate_argnums=(0,))
    init = jax.jit(init_state, out_shardings=nm(sspec))
    G, C, S_sup, S_qry, L_text, n_mod = train_batch_layout(
        cfg, shape, mesh.devices.shape[0]
        if "pod" in mesh.axis_names else 1)

    def make_batch(it: int):
        tasks = make_lm_task_batch(G * C, S_sup, S_qry, L_text,
                                   cfg.vocab_size, seed=it)
        batch = {
            "support": {"tokens": tasks.support_tokens.reshape(
                G, C, S_sup, L_text)},
            "query": {"tokens": tasks.query_tokens.reshape(
                G, C, S_qry, L_text)},
        }
        if cfg.modality:
            rngd = np.random.RandomState(it)
            for part, S in (("support", S_sup), ("query", S_qry)):
                batch[part]["embeds"] = rngd.normal(
                    0, 0.1, (G, C, S, n_mod, cfg.d_model)).astype(
                        jnp.dtype(cfg.dtype))
        return jax.device_put(batch, batch_sh)

    return step, init, make_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--algo", default="fomaml",
                    choices=["maml", "fomaml", "meta-sgd", "meta-sgd-fo",
                             "reptile"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--inner-lr", type=float, default=0.01)
    ap.add_argument("--outer-lr", type=float, default=1e-4)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config + small shape (CPU execution)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    if shape.kind != "train":
        raise SystemExit("use serve.py for inference shapes")

    if args.reduced:
        cfg = reduced_config(cfg)
        shape = dataclasses.replace(shape, seq_len=64, global_batch=4,
                                    clients_per_round=2, seqs_per_client=2)
        mesh = make_device_mesh(jax.devices()[:1])
    else:
        mesh = make_device_mesh()
        n_data = mesh.devices.shape[0]
        full = shape
        shape = per_chip_shape(full, n_data)
        print(f"cut: {full.name} per-chip share on {n_data} chip(s) of the "
              f"data axis: {shape.clients_per_round} clients x "
              f"{shape.seqs_per_client} sequences (of "
              f"{full.seqs_per_client}) x {shape.seq_len} tokens", flush=True)

    step, init, make_batch = build_train(
        cfg, shape, mesh, algo=args.algo, inner_lr=args.inner_lr,
        outer_lr=args.outer_lr)
    state = init(jax.random.PRNGKey(0))
    for it in range(args.steps):
        batch = make_batch(it)
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        routing = record_routing(metrics)
        if (it + 1) % args.log_every == 0:
            print(f"step {it+1:4d}  loss="
                  f"{float(metrics['query_loss']):.4f}  acc="
                  f"{float(metrics['accuracy']):.4f}  "
                  f"({time.perf_counter()-t0:.2f}s)"
                  + (f"  routing={routing}" if routing else ""), flush=True)
    if args.ckpt:
        host_state = jax.device_get(state)
        path = save_server_state(args.ckpt, args.steps, host_state)
        print("checkpoint:", path)


if __name__ == "__main__":
    main()
