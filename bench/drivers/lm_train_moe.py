"""Closed-loop meta-training of a DeepSeek-style LM through the program's
launcher: MLA with YaRN rope, a leading dense layer, and MoE layers that
hold a share of the routed experts.

The timed path is `repro.launch.train.build_train`'s jitted step (FedMeta
rounds over a task batch of clients, state donated), called one round
after another on the traffic's task batches, each put on the device
inside the window; after each round the step's routing counters are
read and recorded in the program's `fedmeta.moe.route` span
(`repro.launch.train.record_routing`). The loop and the checks are
`lm_train`'s: set-up builds the compiled step and its state (weights
made on the device from the seed in one jitted call) and drives it
through the first `checked_rounds` rounds, reading each round's loss,
the first meta-gradient from Adam's first moment and each leaf's change;
after the window the state is freed and the plain reference beside the
configuration follows the same rounds from the same weights.

A round with a non-finite loss, or one whose counters report a dropped
(token, expert) pair, counts as failed: the layer is dropless.

Traffic file keys: clients, support_seqs, query_seqs, seq_len (per
chip), distinct_batches, dialect_frac, stay_prob, checked_rounds.
"""
from __future__ import annotations

import gc
import math
import time

from benchlib import compare, generators, seeds
from benchlib.trace import span

COUNTERS = ("moe_pairs_held", "moe_load_max", "moe_dropped")


def program_config(cfg: dict):
    from repro.configs import ModelConfig
    rs = cfg["rope_scaling"]
    return ModelConfig(
        name=cfg["name"], arch_type="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"],
        d_ff=cfg["moe_intermediate_size"],
        dense_d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], attention="mla",
        kv_lora_rank=cfg["kv_lora_rank"],
        q_lora_rank=cfg["q_lora_rank"] or 0,
        rope_head_dim=cfg["qk_rope_head_dim"], rope_theta=cfg["rope_theta"],
        yarn_factor=float(rs["factor"]),
        yarn_original_max_pos=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=rs["mscale"], yarn_mscale_all_dim=rs["mscale_all_dim"],
        num_experts=cfg["router_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["n_shared_experts"],
        moe_layer_period=cfg["moe_layer_freq"],
        first_k_dense=cfg["first_k_dense_replace"],
        router_scoring=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        router_aux_coef=cfg["aux_loss_alpha"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg["first_held_expert"],
        norm_eps=cfg["rms_norm_eps"], mlp_act="swiglu",
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


ATTN = ("wq", "w_dkv", "w_kpe", "w_uk", "w_uv", "wo")


def _layer_to_program(L: dict, ffn: dict) -> dict:
    return {"norm1": {"scale": L["attn_norm"]},
            "mixer": {**{k: L[k] for k in ATTN},
                      "kv_norm": {"scale": L["kv_norm"]}},
            "norm2": {"scale": L["mlp_norm"]}, "ffn": ffn}


def to_program(p: dict) -> dict:
    """The reference's parameter layout -> the program's LM tree."""
    D, M = p["dense"], p["moe"]
    return {
        "embed": p["embed"],
        "lead_0": _layer_to_program(
            D, {k: D[k] for k in ("w_gate", "w_up", "w_down")}),
        "stack": {"pos0": _layer_to_program(M, {
            "w_router": M["router"], "w_gate": M["e_gate"],
            "w_up": M["e_up"], "w_down": M["e_down"],
            "shared": {"w_gate": M["s_gate"], "w_up": M["s_up"],
                       "w_down": M["s_down"]}})},
        "final_norm": {"scale": p["final_norm"]},
        "lm_head": p["lm_head"],
    }


def _layer_from_program(t: dict) -> dict:
    mixer = t["mixer"]
    return {"attn_norm": t["norm1"]["scale"], "mlp_norm": t["norm2"]["scale"],
            "kv_norm": mixer["kv_norm"]["scale"],
            **{k: mixer[k] for k in ATTN}}


def from_program(t: dict) -> dict:
    lead, s = t["lead_0"], t["stack"]["pos0"]
    ffn = s["ffn"]
    moe = dict(_layer_from_program(s), router=ffn["w_router"],
               e_gate=ffn["w_gate"], e_up=ffn["w_up"], e_down=ffn["w_down"],
               s_gate=ffn["shared"]["w_gate"], s_up=ffn["shared"]["w_up"],
               s_down=ffn["shared"]["w_down"])
    return {"embed": t["embed"], "final_norm": t["final_norm"]["scale"],
            "lm_head": t["lm_head"],
            "dense": dict(_layer_from_program(lead), **lead["ffn"]),
            "moe": moe}


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
        self.mcfg = mcfg = program_config(cfg)
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
        prog = {"losses": [], "routing": []}
        for i in range(checked):
            self.state, met = self.compiled(self.state, self.put(self.host[i]))
            prog["losses"].append(float(met["query_loss"]))
            prog["routing"].append(counters(met))
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
        passed; every round's batch is put on the device inside, and its
        routing counters read and recorded after it."""
        import jax
        from repro.launch.train import record_routing
        losses, routing, n = [], [], 0
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
                    routing.append(record_routing(met))
                n += 1
                if time.perf_counter() - t_start >= seconds:
                    break
            t_end = time.perf_counter()
        tracer.stop()
        return {"t_start": t_start, "window_s": t_end - t_start,
                "rounds": n, "losses": losses, "routing": routing}

    def close(self):
        self.state = self.compiled = None
        gc.collect()


def counters(met) -> dict:
    return {k: int(met[k]) for k in COUNTERS}


# the reference's precision policy by the configuration's dtype: as the
# configuration states it, and the next precision below (the control)
POLICY = {"bfloat16": {"reference": "bf16", "control": "fp8", "half": "bf16"},
          "float32": {"reference": "f32", "control": "bf16", "half": "f32"}}


def follow(ctx, s: Session, variant: str = "reference",
           cache: dict | None = None) -> dict:
    """The plain reference over the session's checked rounds, from the
    same weights: "reference" as the configuration states it, "control"
    in the next precision below, "half" on half of each round's clients
    (a planted fault)."""
    cache = {} if cache is None else cache
    policy = POLICY[ctx.config["dtype"]][variant]
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
    chips, shape, prog, compiled_bytes, mcfg = (
        s.chips, s.shape, s.readings, s.compiled_bytes, s.mcfg)
    s.close()
    ctx.log("window closed; reference follows")
    want = follow(ctx, s)
    ctx.log("reference done")
    checks = compare.training_checks(prog, want, ctx.limits)

    tokens_per_round = shape.global_batch * shape.seq_len
    flops_per_round = ref.fomaml_flops_per_round(
        cfg, traffic["clients"], traffic["support_seqs"] * chips,
        traffic["query_seqs"] * chips, traffic["seq_len"])
    routing = w["routing"]
    pairs = sum(r["moe_pairs_held"] for r in routing)
    # the counters cover each client's query pass; its support pass
    # runs the same layers on sequences of the same dialect
    layer_passes = w["rounds"] * traffic["clients"] * (
        mcfg.num_layers - mcfg.first_k_dense)
    call_flops, call_bytes = ref.expert_call_cost(cfg, pairs / layer_passes)
    return {
        "setup_s": w["t_start"] - ctx.t0,
        "end_to_end": {"tokens_per_s":
                       w["rounds"] * tokens_per_round / w["window_s"]},
        "attempted": w["rounds"],
        "failed": sum(not math.isfinite(x) or r["moe_dropped"] > 0
                      for x, r in zip(w["losses"], routing)),
        "checks": checks,
        "memory": {"peak_bytes_in_use": peak,
                   "compiled_bytes": compiled_bytes},
        "work": {"driver": "lm_train_moe", "rounds": w["rounds"],
                 "window_s": w["window_s"],
                 "flops_per_round": flops_per_round,
                 "tokens_per_round": tokens_per_round,
                 "moe_pairs_held": pairs,
                 "moe_load_max": max(r["moe_load_max"] for r in routing),
                 "moe_dropped": sum(r["moe_dropped"] for r in routing),
                 "expert_call_flops": call_flops,
                 "expert_call_bytes": call_bytes},
        "readings": {"program": prog, "reference": want,
                     "window_routing": routing},
    }
