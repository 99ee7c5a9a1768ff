"""Closed-loop LM meta-training through the program's launcher.

The timed path is `repro.launch.train.build_train`'s jitted step (FedMeta
rounds over a task batch of clients, state donated), called one round
after another on the traffic's task batches, each put on the device
inside the window.

Set-up builds that one compiled step and its state (weights made on the
device from the seed in one jitted call), then drives it through the
first `checked_rounds` rounds, reading each round's loss, the first
meta-gradient from Adam's first moment (m = (1 - b1) g after one step)
and each leaf's change. The same object then runs the window. After the
window the state is freed and the plain reference beside the
configuration follows the same rounds from the same weights.

Traffic file keys: clients, support_seqs, query_seqs, seq_len (per
chip: the data axis splits the sequences), distinct_batches,
dialect_frac, stay_prob, checked_rounds.
"""
from __future__ import annotations

import gc
import math
import time

from benchlib import compare, generators, seeds
from benchlib.trace import span


def program_config(cfg: dict):
    from repro.configs import ModelConfig
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim"), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], mlp_act="swiglu",
        qkv_bias=cfg.get("attention_bias", False),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])


def program_shape(traffic: dict, chips: int):
    """The whole batch across `chips`: each chip of the data axis holds
    `support_seqs + query_seqs` sequences of every client."""
    from repro.configs import InputShape
    per_client = (traffic["support_seqs"] + traffic["query_seqs"]) * chips
    return InputShape("bench", traffic["seq_len"],
                      traffic["clients"] * per_client, "train",
                      clients_per_round=traffic["clients"],
                      seqs_per_client=per_client)


def build_program(cfg: dict, mcfg, shape, mesh):
    """-> (step, init): the program's jitted meta-train step and its
    state initializer."""
    from repro.launch.train import build_train
    step, init, _ = build_train(mcfg, shape, mesh, algo=cfg["algorithm"],
                                inner_lr=cfg["inner_lr"],
                                outer_lr=cfg["outer_lr"])
    return step, init


def to_program(p: dict) -> dict:
    """The reference's parameter layout -> the program's LM tree."""
    L = p["layers"]
    return {
        "embed": p["embed"],
        "final_norm": {"scale": p["final_norm"]},
        "stack": {"pos0": {
            "norm1": {"scale": L["attn_norm"]},
            "mixer": {k: L[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": L["mlp_norm"]},
            "ffn": {k: L[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def from_program(t: dict) -> dict:
    s = t["stack"]["pos0"]
    layers = {"attn_norm": s["norm1"]["scale"],
              "mlp_norm": s["norm2"]["scale"], **s["mixer"], **s["ffn"]}
    return {"embed": t["embed"], "final_norm": t["final_norm"]["scale"],
            "layers": layers}


def _check_layout(made, want):
    import jax
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), made)
    exp = jax.tree.map(lambda x: (x.shape, str(x.dtype)), want)
    if got != exp:
        raise RuntimeError(f"the program's state layout changed: the "
                           f"benchmark makes {got}, the step takes {exp}")


class Session:
    """Set-up's one object: the compiled step, its state and the traffic,
    driven through the checked rounds (`readings`)."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_device_mesh
        from repro.launch.steps import input_specs

        cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
        self.chips = chips = len(ctx.devices)
        mcfg = program_config(cfg)
        self.shape = shape = program_shape(traffic, chips)
        mesh = make_device_mesh(ctx.devices)
        step, init = build_program(cfg, mcfg, shape, mesh)

        sds = jax.eval_shape(init, jax.random.PRNGKey(0))
        shardings = jax.tree.map(lambda s: s.sharding, sds)
        key = seeds.weight_key_data(ctx.seed)

        def make_theta(key_data):
            return to_program(ref.init_params(key_data, cfg))

        _check_layout(jax.eval_shape(make_theta, key), sds["phi"]["theta"])
        theta = jax.jit(make_theta, out_shardings=shardings["phi"]["theta"])(
            key)
        # the weights as made, kept on the host: the change of every leaf
        # is taken against them, and the reference starts from them
        self.theta0 = jax.device_get(theta)
        opt = jax.jit(lambda: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), sds["opt"]),
            out_shardings=shardings["opt"])()
        self.state = {"phi": {"theta": theta}, "opt": opt}
        del theta
        ctx.log("weights made")

        # the traffic: whole batches across the chips; rows all differ
        self.host = generators.lm_task_batches(
            seeds.traffic_rng(ctx.seed),
            dict(traffic, support_seqs=traffic["support_seqs"] * chips,
                 query_seqs=traffic["query_seqs"] * chips),
            cfg["vocab_size"])
        pspec = input_specs(mcfg, shape, mesh)["pspec"]
        self._bsh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                                 is_leaf=lambda x: isinstance(x, P))

        self.compiled = step.lower(self.state,
                                   self.put(self.host[0])).compile()
        ctx.log("step compiled")
        ma = self.compiled.memory_analysis()
        self.compiled_bytes = int(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)

        norms = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
            from_program(t)))
        delta = jax.jit(lambda t, t0: norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            t, t0)))

        def flat(tree):
            return {k: float(v) for k, v in ref.flatten(tree).items()}

        self.checked = checked = traffic["checked_rounds"]
        b1 = cfg["adam_b1"]
        prog = {"losses": []}
        for i in range(checked):
            self.state, met = self.compiled(self.state, self.put(self.host[i]))
            prog["losses"].append(float(met["query_loss"]))
            if i == 0:
                g = flat(norms(self.state["opt"]["m"]["theta"]))
                prog["grad_norms"] = {k: v / (1 - b1) for k, v in g.items()}
            ctx.log(f"checked round {i + 1}")
        prog["delta_norms"] = flat(delta(
            self.state["phi"]["theta"],
            jax.device_put(self.theta0, shardings["phi"]["theta"])))
        self.readings = prog

    def put(self, b):
        import jax
        return jax.device_put({"support": {"tokens": b["support"][None]},
                               "query": {"tokens": b["query"][None]}},
                              self._bsh)

    def window(self, seconds: float, tracer) -> dict:
        """Closed loop, one round after another, until `seconds` have
        passed; every round's batch is put on the device inside."""
        import jax
        losses, n = [], 0
        tracer.start()
        with span("bench.window"):
            t_start = time.perf_counter()
            while True:
                with span("bench.put_batch"):
                    batch = self.put(self.host[(self.checked + n)
                                               % len(self.host)])
                with span("bench.step"):
                    self.state, met = self.compiled(self.state, batch)
                with span("bench.wait"):
                    jax.block_until_ready((self.state, met))
                    losses.append(float(met["query_loss"]))
                n += 1
                if time.perf_counter() - t_start >= seconds:
                    break
            t_end = time.perf_counter()
        tracer.stop()
        return {"t_start": t_start, "window_s": t_end - t_start,
                "rounds": n, "losses": losses}

    def close(self):
        self.state = self.compiled = None
        gc.collect()


POLICY = {"reference": "bf16", "control": "fp8", "half": "bf16"}


def follow(ctx, s: Session, variant: str = "reference",
           cache: dict | None = None) -> dict:
    """The plain reference over the session's checked rounds, from the
    same weights: "reference" as the configuration states it, "control"
    in the next precision below, "half" on half of each round's clients
    (a planted fault)."""
    cache = {} if cache is None else cache
    policy = POLICY[variant]
    if policy not in cache:
        cache[policy] = ctx.reference.Reference(ctx.config, policy)
    batches = s.host[:s.checked]
    if variant == "half":
        batches = [{k: v[:v.shape[0] // 2] for k, v in b.items()}
                   for b in batches]
    return cache[policy].run(from_program(s.theta0), batches, s.checked)


def run(ctx) -> dict:
    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
    s = Session(ctx)
    w = s.window(ctx.seconds, ctx.tracer)
    peak = ctx.memory_peak()
    chips, shape, prog, compiled_bytes = (
        s.chips, s.shape, s.readings, s.compiled_bytes)
    s.close()
    ctx.log("window closed; reference follows")
    want = follow(ctx, s)
    ctx.log("reference done")
    checks = compare.training_checks(prog, want, ctx.limits)

    tokens_per_round = shape.global_batch * shape.seq_len
    flops_per_round = ref.fomaml_flops_per_round(
        cfg, traffic["clients"], traffic["support_seqs"] * chips,
        traffic["query_seqs"] * chips, traffic["seq_len"])
    return {
        "setup_s": w["t_start"] - ctx.t0,
        "end_to_end": {"tokens_per_s":
                       w["rounds"] * tokens_per_round / w["window_s"]},
        "attempted": w["rounds"],
        "failed": sum(not math.isfinite(x) for x in w["losses"]),
        "checks": checks,
        "memory": {"peak_bytes_in_use": peak,
                   "compiled_bytes": compiled_bytes},
        "work": {"driver": "lm_train", "rounds": w["rounds"],
                 "window_s": w["window_s"],
                 "flops_per_round": flops_per_round,
                 "tokens_per_round": tokens_per_round},
        "readings": {"program": prog, "reference": want},
    }
