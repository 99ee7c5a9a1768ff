"""Paper-scale FedMeta rounds through the program's experiment plane.

The timed path is `federated.experiment.make_trainer`'s
`FederatedTrainer` on the plan's `client_plane` pipeline (FOMAML, the
platform's Pallas kernels, donated state) and its `run`: host task
sampling and staging, the packed client plane's inner update, the
weighted aggregate and the fused Adam, and the per-round loss read-back
(`flush_every`).

Set-up builds the trainer over the traffic's synthetic writers and the
weights made from the seed, and runs the first `checked_rounds` rounds
through `run`, reading each round's loss, the first meta-gradient from
Adam's first moment and each leaf's change. The same trainer and state
then run the window, `rounds_per_call` rounds per `run` call, until
`--seconds` have passed. The plain reference replays the checked rounds
afterwards with the same task draw.

Traffic file keys: train_writers, mean_samples, image_size,
clients_per_round, support_frac, support_size, query_size, flush_every,
prefetch_depth, rounds_per_call, checked_rounds.
"""
from __future__ import annotations

import functools
import gc
import math
import time

from benchlib import compare, generators, seeds
from benchlib.trace import span

TASK_STREAM = 2


def task_seed(seed: int) -> int:
    """The trainer's sampling seed (a 32-bit word, as RandomState takes)."""
    return int(seeds.seed_sequence(seed, TASK_STREAM).generate_state(1)[0])


def build_trainer(cfg: dict, traffic: dict, writers, seed: int):
    """The program's trainer for the plan, as `make_trainer` builds it."""
    from repro.core import classification_loss
    from repro.data.federated import ClientData
    from repro.federated.experiment import default_plan, make_trainer
    from repro.models.paper import femnist_cnn

    model = femnist_cnn(num_classes=cfg["num_classes"],
                        image_size=cfg["image_size"], hidden=cfg["hidden"])
    plan = default_plan(
        "femnist", pipeline="client_plane",
        clients_per_round=traffic["clients_per_round"],
        support_frac=traffic["support_frac"],
        support_size=traffic["support_size"],
        query_size=traffic["query_size"], num_clients=len(writers),
        seed=task_seed(seed), flush_every=traffic["flush_every"],
        prefetch_depth=traffic["prefetch_depth"],
        method_overrides={cfg["algorithm"]: {
            "inner_lr": cfg["inner_lr"], "outer_lr": cfg["outer_lr"],
            "inner_steps": cfg["inner_steps"]}})
    loss_fn, eval_fn = classification_loss(model.apply)
    clients = [ClientData(x, y) for x, y in writers]
    return make_trainer(plan, cfg["algorithm"], loss_fn, eval_fn, clients)


def _norms(tree) -> dict:
    import jax.numpy as jnp
    return {f"{a}.{b}": float(jnp.sqrt(jnp.sum(jnp.square(v))))
            for a, sub in tree.items() for b, v in sub.items()}


class Session:
    def __init__(self, ctx):
        import jax

        cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
        self.precision = cfg["matmul_precision"]
        self.writers = generators.femnist_writers(
            seeds.traffic_rng(ctx.seed), traffic, cfg["num_classes"])
        ctx.log("writers made")
        theta = jax.jit(functools.partial(ref.init_params, cfg=cfg))(
            seeds.weight_key_data(ctx.seed))
        self.theta0 = jax.device_get(theta)
        self.tr = tr = build_trainer(cfg, traffic, self.writers, ctx.seed)
        with jax.default_matmul_precision(self.precision):
            state = tr.init(None, lambda _key: theta)
        del theta
        self.per_call = traffic["rounds_per_call"]
        self.checked = n = traffic["checked_rounds"]
        b1 = cfg["adam_b1"]
        prog = {}
        with jax.default_matmul_precision(self.precision):
            state = jax.block_until_ready(tr.run(state, 1))
            ctx.log("first round (compiles)")
            m = tr._plane.unpack(state["opt"]["m"])["theta"]
            prog["grad_norms"] = {k: v / (1 - b1) for k, v in
                                  _norms(m).items()}
            state = jax.block_until_ready(tr.run(state, n, start_round=1))
        prog["losses"] = [r["query_loss"] for r in tr.history[:n]]
        delta = jax.tree.map(lambda a, b: a - b, tr.phi_tree(state)["theta"],
                             jax.device_put(self.theta0))
        prog["delta_norms"] = _norms(delta)
        self.readings = prog
        self.state, self.round = state, n
        ctx.log(f"checked rounds {n}")

    def window(self, seconds: float, tracer) -> dict:
        import jax
        done = 0
        tracer.start()
        with jax.default_matmul_precision(self.precision), \
                span("bench.window"):
            t_start = time.perf_counter()
            while True:
                with span("bench.run_rounds"):
                    end = self.round + self.per_call
                    self.state = self.tr.run(self.state, end,
                                             start_round=self.round)
                with span("bench.wait"):
                    jax.block_until_ready(self.state)
                self.round, done = end, done + self.per_call
                if time.perf_counter() - t_start >= seconds:
                    break
            t_end = time.perf_counter()
        tracer.stop()
        losses = [r["query_loss"] for r in self.tr.history[-done:]]
        return {"t_start": t_start, "window_s": t_end - t_start,
                "rounds": done, "losses": losses}

    def close(self):
        self.state = None
        gc.collect()


PRECISION = {"reference": None, "control": "high", "half": None}


def _half(tasks):
    """Half of the round's writers left out; the rest re-weighted."""
    *arrays, w = tasks
    keep = len(w) // 2
    return (*[a[:keep] for a in arrays], w[:keep] / w[:keep].sum())


def follow(ctx, s: Session, variant: str = "reference",
           cache: dict | None = None) -> dict:
    """The plain reference over the session's checked rounds, from the
    same weights and task draw: "reference" at the configuration's
    precision, "control" at the next below ("high", three bfloat16
    passes), "half" with half of each round's writers left out."""
    cache = {} if cache is None else cache
    precision = PRECISION[variant]
    if precision not in cache:
        cache[precision] = ctx.reference.Reference(ctx.config, precision)
    return cache[precision].run(
        s.theta0, s.writers, ctx.traffic, task_seed(ctx.seed), s.checked,
        keep=_half if variant == "half" else None)


def run(ctx) -> dict:
    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
    s = Session(ctx)
    w = s.window(ctx.seconds, ctx.tracer)
    peak = ctx.memory_peak()
    s.close()
    ctx.log("window closed; reference follows")
    want = follow(ctx, s)
    ctx.log("reference done")
    checks = compare.training_checks(s.readings, want, ctx.limits)
    return {
        "setup_s": w["t_start"] - ctx.t0,
        "end_to_end": {"round_s": w["window_s"] / w["rounds"]},
        "attempted": w["rounds"],
        "failed": sum(not math.isfinite(x) for x in w["losses"]),
        "checks": checks,
        "memory": {"peak_bytes_in_use": peak},
        "work": {"driver": "paper_rounds", "rounds": w["rounds"],
                 "window_s": w["window_s"],
                 "flops_per_round": ref.fomaml_flops_per_round(cfg, traffic),
                 "kernel_bytes": ref.kernel_bytes(cfg, traffic)},
        "readings": {"program": s.readings, "reference": want},
    }
