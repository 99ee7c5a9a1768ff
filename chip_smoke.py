#!/usr/bin/env python3
"""Chip smoke: the system's main paths, once each, on a TPU.

    python3 chip_smoke.py               # one chip: phases a, b, c
    python3 chip_smoke.py --four-chips  # four chips: the sharded paths
                                        # against one chip, nothing else

One process drives the chip, in order:

  a  LM meta-training: smollm-360m at published widths (32 layers,
     d 960, vocab 49152) through `launch.train`'s step, FOMAML, 3 steps
     on one chip's share of train_4k. Every loss is finite and the first
     is within 0.5 of ln(vocab) (random weights predict near-uniformly).
  b  Paper-scale rounds: the femnist plan through the packed client
     plane with the Pallas kernels and buffer donation, then the same
     rounds on the XLA oracles; the two φ must agree.
  c  Serving: `launch.serve.build_engine` for smollm-360m at published
     widths answers 4 `TrafficModel` requests in two windows (the
     second window is all cache hits) with the Pallas decode; every
     served row equals `jax.jit(algo.adapt)` for its client.

`--four-chips` runs the femnist round on the sharded client axis over
four chips against the vmap axis on one, and the LM step on a 4-chip
data mesh against the same batch on one chip.

Each phase prints its kernel impls, compile and run seconds, the
device's `peak_bytes_in_use` (the process's running peak) and the
seconds since the script started. The last line
is one JSON object naming the device. Without a TPU, or if any phase
fails, the script exits non-zero and prints no such line. These are
smoke timings, not benchmark results.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FEMNIST_ROUNDS = 3
T0 = time.perf_counter()


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def report(name: str, dev, *, impl: dict, compile_s: float, run_s: float,
           **extra) -> None:
    fields = {"phase": name, "impl": impl, "compile_s": compile_s,
              "run_s": run_s, "peak_bytes_in_use": peak_bytes(dev),
              "elapsed_s": time.perf_counter() - T0, **extra}
    log(f"phase {name}: " + json.dumps(fields))


def assert_phi_agrees(got, ref, *, moved: float, what: str,
                      allowance: float = 1e-4) -> dict:
    """Elementwise agreement of two flat φ after the same rounds.

    The two runs do the same f32 arithmetic in different orders (the
    aggregate's sum, fusion of θ − α·g), so values differ by a few ulps:
    atol 1e-5 is 1/300 of the most a coordinate moves in 3 Adam steps
    of lr 1e-3. Adam's early steps move a coordinate by ±lr whatever its
    gradient's size, so a near-zero meta-gradient that the two round to
    opposite signs moves by up to 2·lr per round: at most `allowance` of
    the coordinates may do that. The count is the check that separates
    faults; `moved` is a sanity bound only, since about that much is all
    two runs of the same rounds can differ by."""
    got, ref = np.asarray(got), np.asarray(ref)
    diff = np.abs(got - ref)
    off = int(np.sum(diff > 1e-5 + 1e-5 * np.abs(ref)))
    out = {"max_abs_diff": float(diff.max()), "coords_beyond_tol": off,
           "coords": int(diff.size)}
    if off > allowance * diff.size or diff.max() > moved:
        raise AssertionError(f"{what}: φ disagrees: {out}")
    return out


# ------------------------------------------------------------------ (a)

def phase_lm_train(dev, cfg, shape, *, steps: int = 3):
    import jax

    from repro.launch.mesh import make_device_mesh
    from repro.launch.train import build_train

    mesh = make_device_mesh([dev])
    step, init, make_batch = build_train(cfg, shape, mesh, algo="fomaml")
    state = init(jax.random.PRNGKey(0))
    batches = [make_batch(it) for it in range(steps)]
    t0 = time.perf_counter()
    compiled = step.lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    losses = []
    t0 = time.perf_counter()
    for batch in batches:
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["query_loss"]))
    run_s = time.perf_counter() - t0
    del state
    ln_v = math.log(cfg.vocab_size)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite LM loss: {losses}")
    if abs(losses[0] - ln_v) > 0.5:
        raise AssertionError(f"first loss {losses[0]} is not within 0.5 "
                             f"of ln(vocab) = {ln_v}")
    # the step's inner update, attention and Adam run on XLA by design
    # (launch/steps.py says why); pallas_calls counts what is left
    report("a:lm_train", dev,
           impl={"meta_update": "xla", "attention": "xla", "adam": "xla"},
           compile_s=compile_s, run_s=run_s, steps=steps, losses=losses,
           ln_vocab=ln_v, pallas_calls=n_kernels)


# ------------------------------------------------------------------ (b)

def femnist_setup():
    from repro.core import classification_loss
    from repro.federated.experiment import DATASETS, default_plan

    plan = default_plan("femnist", pipeline="client_plane")
    su = DATASETS["femnist"]
    train, _, _ = su["data"](plan.num_clients, plan.seed).split_clients(
        seed=plan.seed)
    model = su["model"]()
    return plan, train, model, classification_loss(model.apply)


def femnist_rounds(setup, *, impl: str, staged=None, **trainer_kw):
    """The femnist plan's FOMAML trainer for FEMNIST_ROUNDS rounds ->
    (trainer, φ0, φ, query losses, first-round s (compile + run),
    later rounds s). With a list `staged`, each round appends the
    (shape, sharding) of every state and input leaf its step was
    called with."""
    import jax

    from repro.federated.experiment import make_trainer

    plan, train, model, (loss_fn, eval_fn) = setup
    tr = dataclasses.replace(
        make_trainer(plan, "fomaml", loss_fn, eval_fn, train), impl=impl,
        **trainer_kw)
    state = tr.init(jax.random.PRNGKey(plan.seed), model.init)
    phi0 = np.asarray(state["phi"])
    if staged is not None:
        step = tr._step

        def recorded(st, *args):
            staged.append(tuple([(x.shape, x.sharding)
                                 for x in jax.tree.leaves(t)]
                                for t in (st, args)))
            return step(st, *args)
        tr._step = recorded
    t0 = time.perf_counter()
    state = jax.block_until_ready(tr.run(state, 1))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = jax.block_until_ready(
        tr.run(state, FEMNIST_ROUNDS, start_round=1))
    rest_s = time.perf_counter() - t0
    losses = [r["query_loss"] for r in tr.history]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite femnist loss: {losses}")
    return tr, phi0, np.asarray(state["phi"]), losses, first_s, rest_s


def kernel_agreement(n: int = 1 << 20, m: int = 8) -> dict:
    """Each phase-b kernel against its XLA oracle on random f32 inputs:
    the largest relative gap. The inner update and Adam are elementwise
    (a gap is an ulp of rounding); the aggregate's sum may run in
    another order (a few ulps of the sum)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.meta_update import ops as mu_ops
    from repro.optim.fused_adam import adam_flat_update

    rng = np.random.RandomState(0)
    theta, g = (jnp.asarray(rng.normal(0, 1, (m, n)), jnp.float32)
                for _ in range(2))
    w = jnp.asarray(rng.uniform(0.5, 2.0, (m,)), jnp.float32)
    v = jnp.square(g[1])

    def gap(fn):
        got, ref = fn("pallas"), fn("xla")
        return max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
                   for a, b in zip(jax.tree.leaves(got),
                                   jax.tree.leaves(ref)))

    gaps = {
        "inner_update": gap(lambda i: mu_ops.inner_update(
            theta, 0.05, g, impl=i)),
        "weighted_aggregate": gap(lambda i: mu_ops.weighted_aggregate(
            g, w, impl=i)),
        "adam": gap(lambda i: adam_flat_update(
            theta[0], g[0], g[1], v, jnp.int32(3), lr=1e-3, impl=i)[:3]),
    }
    if max(gaps.values()) > 1e-5:
        raise AssertionError(f"a kernel disagrees with its oracle: {gaps}")
    return gaps


def phase_femnist(dev):
    import jax

    gaps = kernel_agreement()
    log(f"kernel vs oracle, largest relative gap: {gaps}")
    setup = femnist_setup()
    # f32 matmuls: at the TPU's default (bf16-pass) precision a 1-ulp
    # difference in θ_u turns into bf16-sized gradient noise, and the
    # comparison would measure that noise instead of the kernels
    with jax.default_matmul_precision("highest"):
        _, phi0, phi_p, loss_p, first_p, rest_p = femnist_rounds(
            setup, impl="pallas")
        _, _, phi_x, loss_x, first_x, rest_x = femnist_rounds(
            setup, impl="xla")
    log(f"femnist query losses: pallas {loss_p}, xla {loss_x}")
    lr = setup[0].outer_lr
    agree = assert_phi_agrees(phi_p, phi_x, moved=2 * lr * FEMNIST_ROUNDS,
                              what="femnist pallas vs xla")
    report("b:femnist_rounds", dev,
           impl={"meta_update": "pallas", "aggregate": "pallas",
                 "adam": "pallas"},
           compile_s=first_p - rest_p / (FEMNIST_ROUNDS - 1),
           run_s=rest_p, first_round_s=first_p, rounds=FEMNIST_ROUNDS,
           donated=True, matmul_precision="highest", kernel_gaps=gaps,
           losses=loss_p, xla_losses=loss_x, xla_first_round_s=first_x,
           xla_run_s=rest_x, n_params=int(phi0.size),
           max_phi_moved=float(np.abs(phi_p - phi0).max()), **agree)


# ------------------------------------------------------------------ (c)

def phase_serving(dev, cfg, *, adapt_batch: int = 2, cache_capacity: int = 2,
                  support_len: int = 64, prompt_len: int = 32,
                  new_tokens: int = 4):
    import jax
    import jax.numpy as jnp

    from repro.federated.serving import TrafficModel
    from repro.kernels.attention import ops as attn_ops
    from repro.kernels.meta_update import ops as mu_ops
    from repro.launch.serve import build_engine
    from repro.models import init_lm

    log(f"serving: adapt_batch={adapt_batch} cache_capacity="
        f"{cache_capacity} (a cached row is one f32 φ plane; the adapt "
        f"batch and the decode group each hold such rows, sized to fit "
        f"16 GB)")
    phi = {"theta": init_lm(jax.random.PRNGKey(0), cfg)}
    engine = build_engine(cfg, phi, adapt_batch=adapt_batch,
                          cache_capacity=cache_capacity,
                          decode_impl="pallas")
    # seed 0 draws clients [1, 0, 0, 1]: window one is two misses (one
    # adapt flush), window two two cache hits
    tm = TrafficModel(num_clients=2, rate=4.0, support_sizes=(2,), seed=0)
    vocab = cfg.vocab_size
    reqs = tm.requests(
        4, lambda r, n: jnp.asarray(r.randint(0, vocab, (n, support_len)),
                                    jnp.int32),
        lambda r: jnp.asarray(r.randint(0, vocab, (prompt_len,)), jnp.int32))
    t0 = time.perf_counter()
    first = engine.serve(reqs[:2], max_new_tokens=new_tokens)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = engine.serve(reqs[2:], max_new_tokens=new_tokens)
    second_s = time.perf_counter() - t0
    records = first.records + second.records
    hits = sum(r["hit"] for r in records)
    if len(records) != 4 or hits < 1:
        raise AssertionError(f"served {len(records)} requests with {hits} "
                             f"cache hits")
    for r in records:
        toks = np.asarray(r["tokens"])
        if toks.shape != (new_tokens,) or toks.min() < 0 or \
                toks.max() >= vocab:
            raise AssertionError(f"request {r['rid']}: tokens {toks}")
    # the contract: a served row is the client's solo jit(adapt)
    adapt = jax.jit(engine.algo.adapt)
    by_rid = {rec["rid"]: rec for rec in records}
    by_client = {}
    for req in reqs:
        by_client.setdefault(req.client, (req, by_rid[req.rid]))
    t0 = time.perf_counter()
    for client, (req, rec) in sorted(by_client.items()):
        want = adapt(phi, req.support)
        got = engine.unpack_row(rec["row"])
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            if not bool(jnp.array_equal(w, g)):
                gap = float(jnp.max(jnp.abs(w.astype(jnp.float32)
                                            - g.astype(jnp.float32))))
                raise AssertionError(f"client {client}: served row differs "
                                     f"from jit(adapt) (max gap {gap} in "
                                     f"a {w.shape} leaf)")
        del want, got
    ref_s = time.perf_counter() - t0
    report("c:serving", dev,
           impl={"meta_update": mu_ops.resolve_impl(),
                 "attention": attn_ops.resolve_impl(),
                 "decode_attention": "pallas"},
           compile_s=first_s - second_s, run_s=second_s,
           requests=len(records), hits=hits, new_tokens=new_tokens,
           first_window_s=first_s, reference_s=ref_s,
           adapt_batch=adapt_batch, cache_capacity=cache_capacity,
           clients_checked=len(by_client))


# ------------------------------------------------------------ four chips

def phase_sharded_rounds(devs):
    import jax

    from repro.sharding.context import make_mesh

    setup = femnist_setup()
    mesh = make_mesh((len(devs),), ("clients",), devices=devs)
    # 3-pass bf16 matmuls (about 16 mantissa bits), not f32: compiled
    # ahead of time for a described 4-chip v5e, the sharded round took
    # 147 s at "highest" and 17 s at "high". At "high" a 1-ulp difference
    # in an operand moves a product by up to 2^-16 of itself, so a
    # meta-gradient within that of zero may round to the other sign:
    # 1 in 10^3 coordinates may flip (phase_femnist gives the rest)
    staged = []
    with jax.default_matmul_precision("high"):
        tr, _, phi_s, loss_s, first_s, rest_s = femnist_rounds(
            setup, impl="pallas", staged=staged, client_axis="sharded",
            mesh=mesh)
        _, _, phi_v, loss_v, first_v, rest_v = femnist_rounds(
            setup, impl="pallas")
    log(f"femnist query losses: sharded {loss_s}, vmap {loss_v}")
    # what the step was called with, every round: the state replicated
    # on every chip, and each client-axis input split over the chips
    for r, (state_leaves, input_leaves) in enumerate(staged):
        for shape, sh in state_leaves:
            if len(sh.device_set) != len(devs) or \
                    not sh.is_fully_replicated:
                raise AssertionError(f"round {r}: state placed as {sh}")
        for shape, sh in input_leaves:
            if sh.shard_shape(shape)[0] * len(devs) != shape[0]:
                raise AssertionError(f"round {r}: a {shape} input staged "
                                     f"as {sh}")
    spread = len(staged[-1][1][0][1].device_set)
    lr = setup[0].outer_lr
    agree = assert_phi_agrees(phi_s, phi_v, moved=2 * lr * FEMNIST_ROUNDS,
                              what="sharded vs vmap", allowance=1e-3)
    report("4:femnist_sharded", devs[0],
           impl={"meta_update": "pallas", "aggregate": "pallas",
                 "adam": "pallas"},
           compile_s=first_s - rest_s / (FEMNIST_ROUNDS - 1), run_s=rest_s,
           chips=len(devs), staged_on_devices=spread,
           rounds_checked=len(staged), losses=loss_s,
           matmul_precision="high",
           vmap_losses=loss_v, vmap_run_s=rest_v, **agree)


def phase_sharded_lm(devs, cfg, shape):
    import jax

    from repro.launch.mesh import make_device_mesh
    from repro.launch.train import build_train

    def one_step(chips):
        mesh = make_device_mesh(chips)
        step, init, make_batch = build_train(cfg, shape, mesh, algo="fomaml")
        state = init(jax.random.PRNGKey(0))
        batch = make_batch(0)
        phi0 = jax.device_get(state["phi"]["theta"])
        t0 = time.perf_counter()
        compiled = step.lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["query_loss"])
        first_s = time.perf_counter() - t0
        phi1 = jax.device_get(state["phi"]["theta"])
        # a second step on the same batch: the first call's time holds
        # one-off costs (transfers, allocation)
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(state, batch))
        run_s = time.perf_counter() - t0
        log(f"lm step on {len(chips)} chip(s): compile {compile_s} s, first "
            f"call {first_s} s, second {run_s} s, elapsed "
            f"{time.perf_counter() - T0} s")
        return phi0, phi1, loss, compile_s, first_s, run_s

    a0, a1, loss_1, _, first_1, run_1 = one_step(devs[:1])
    b0, b1, loss_4, compile_4, first_4, run_4 = one_step(devs)
    f32 = lambda t: np.concatenate(   # noqa: E731
        [np.asarray(x, np.float32).ravel() for x in jax.tree.leaves(t)])
    a0, a1, b0, b1 = f32(a0), f32(a1), f32(b0), f32(b1)
    if not np.array_equal(a0, b0):
        raise AssertionError("the 1-chip and 4-chip inits differ")
    # the data-parallel split reorders bf16 sums: the losses agree to
    # bf16 rounding (2e-3). Adam's first step moves each coordinate by
    # ±lr by its gradient's sign, so the two updates match except where
    # a gradient within rounding of zero takes the other sign: their
    # cosine stays above 0.95 unless more than ~2.5% of them flip
    rel_loss = abs(loss_4 - loss_1) / abs(loss_1)
    d1, d4 = a1 - a0, b1 - a0
    cosine = float(np.dot(d1, d4) / (np.linalg.norm(d1) * np.linalg.norm(d4)))
    differ = float(np.mean(a1 != b1))
    if not math.isfinite(loss_4) or rel_loss > 2e-3 or cosine < 0.95:
        raise AssertionError(f"4-chip LM step disagrees: loss {loss_4} vs "
                             f"{loss_1}, update cosine {cosine}")
    report("4:lm_step_mesh", devs[0],
           impl={"meta_update": "xla", "attention": "xla", "adam": "xla"},
           compile_s=compile_4, run_s=run_4, first_call_s=first_4,
           chips=len(devs), loss=loss_4, one_chip_loss=loss_1,
           one_chip_run_s=run_1, one_chip_first_call_s=first_1,
           rel_loss_diff=rel_loss, update_cosine=cosine,
           frac_params_differ=differ)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on four chips")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips; JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    devs = devs[:want]
    cache_dir = enable_compile_cache()

    from repro.configs import INPUT_SHAPES, get_config
    from repro.kernels.dispatch import platform_impl
    from repro.launch.train import per_chip_shape

    log(f"device: {devs[0].device_kind} x{len(devs)}  platform impl: "
        f"{platform_impl()}  compile cache: {cache_dir}")
    cfg = get_config("smollm-360m")
    if args.four_chips:
        phase_sharded_rounds(devs)
        # the same batch must also fit one chip: the 4-chip share of
        # train_4k (8 sequences per client) at 1024 tokens holds what
        # one chip's share holds at 4096
        shape = dataclasses.replace(
            per_chip_shape(INPUT_SHAPES["train_4k"], len(devs)),
            seq_len=1024)
        log(f"cut: train_4k on {len(devs)} chips vs 1: "
            f"{shape.clients_per_round} clients x {shape.seqs_per_client} "
            f"sequences x {shape.seq_len} tokens (of 4096)")
        phase_sharded_lm(devs, cfg, shape)
    else:
        dev = devs[0]
        shape = per_chip_shape(INPUT_SHAPES["train_4k"], 1)
        log(f"cut: train_4k per-chip share: {shape.clients_per_round} "
            f"clients x {shape.seqs_per_client} sequences (of 32) x "
            f"{shape.seq_len} tokens; smollm-360m at published widths, "
            f"{cfg.num_layers} layers")
        phase_lm_train(dev, cfg, shape)
        phase_femnist(dev)
        phase_serving(dev, cfg)
    log(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
