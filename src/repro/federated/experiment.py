"""Scenario plane: FedMeta vs FedAvg on any registered workload, under
identical conditions.

The paper's headline claims (Fig. 3 / §4, Table 3 / §4.3) are
*comparisons*: FedMeta reaches a target accuracy with 2.82–4.33× less
communication than FedAvg, with higher final accuracy — and on the
production recommendation workload a small per-client local-head model
beats FedAvg's global-service classifier on both accuracy and bytes. A
comparison is only meaningful when every method runs under the same
client split, the same per-round client sampling stream, and honest
per-method communication accounting — the evaluation discipline urged by
Li et al. (2019). This module is the one place that enforces those
invariants:

  * one `FederatedDataset`, one `split_clients(seed)` call, shared by
    every method; scenarios may expose a per-method *view* of it (e.g.
    the recommend scenario's local-label view for FedMeta) but views
    preserve client order and sizes, so sampling streams stay identical;
  * every trainer consumes an identical task-sampling stream: one
    `sample_task_batch` per round from a `RandomState(seed)` that both
    `FederatedTrainer` and `FedAvgTrainer` advance with the exact same
    call pattern (FedAvg's local minibatch indices come from a separate
    stream), so round r samples the same clients for every method;
  * per-round history (train loss, eval accuracy, cumulative
    upload/download bytes, client GFLOPs) recorded by the trainers
    themselves at full round resolution — with per-METHOD θ sizes, so a
    method shipping a smaller model pays fewer bytes per round
    (`CommTracker.phi_MB`, the paper's §4.3 size argument);
  * the paper's comm-to-target-accuracy metric (`comm_to_target`)
    computed from those histories against one shared target;
  * per-method fairness accounting (`fairness_stats`): the distribution
    of per-client accuracies at final eval — deciles, variance, and the
    worst-10% mean — following the federated-fairness lens of Li et
    al.'s survey.

`run_comparison(plan)` is the entry point; it emits a JSON artifact
under ``results/experiments/`` with the full curves, the comm-to-target
table, and the fairness blocks (schema documented field-by-field in
DESIGN.md §13).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Optional, Sequence

import jax

from repro.analysis.sanitizers import assert_no_tracers, sanitizers_enabled
from repro.federated.fedavg import FedAvgTrainer
from repro.federated.faults import FaultConfig
from repro.federated.population import UnreliabilityConfig
from repro.federated.privacy import DPConfig
from repro.kernels.meta_update.compress import CompressionConfig
from repro.federated.server import (FederatedTrainer, evaluate_global,
                                    evaluate_meta)

FEDMETA_METHODS = ("maml", "fomaml", "meta-sgd", "reptile")
FEDAVG_METHODS = ("fedavg", "fedavg(meta)")
PIPELINES = ("tree", "client_plane")    # FedMeta: reference | flat
DEFAULT_METHODS = FEDAVG_METHODS + ("maml", "fomaml", "meta-sgd")


def _femnist_data(num_clients, seed, **lazy_kw):
    from repro.data import make_femnist
    return make_femnist(num_clients=num_clients, mean_samples=60, seed=seed, **lazy_kw)


def _femnist_model():
    from repro.models.paper import femnist_cnn
    return femnist_cnn(num_classes=62, hidden=128)


def _sent140_data(num_clients, seed, **lazy_kw):
    from repro.data import make_sent140
    return make_sent140(num_clients=num_clients, seed=seed, **lazy_kw)


def _sent140_model():
    from repro.models.paper import sent_lstm
    return sent_lstm(vocab=2000, hidden=32, embed_dim=16)


def _shakespeare_data(num_clients, seed, **lazy_kw):
    from repro.data import make_shakespeare
    return make_shakespeare(num_clients=num_clients, mean_samples=150,
                            seed=seed, **lazy_kw)


def _shakespeare_model():
    from repro.models.paper import char_lstm
    return char_lstm(vocab=70, hidden=64, embed_dim=8)


# ---- recommend scenario (paper §4.3 / Table 3) --------------------------
# Scaled constants of the synthetic production dataset: the paper has
# 2,400 services with 2–36 per client and a 40-way local head; we keep
# the 40-way head and the 2–36-per-client structure over a 120-service
# catalogue (data/synth_recommend.py).
REC_SERVICES, REC_CTX, REC_HEAD = 120, 24, 40
REC_FEAT = REC_CTX + REC_SERVICES


def _recommend_data(num_clients, seed, **lazy_kw):
    from repro.data import make_recommend
    return make_recommend(num_clients=num_clients, num_services=REC_SERVICES,
                          ctx_dim=REC_CTX, seed=seed, **lazy_kw)


def _recommend_model():
    """The GLOBAL-head recommender FedAvg must ship: one classifier over
    the whole service catalogue (the paper's 2420-way MIXED model)."""
    from repro.models.paper import rec_nn
    return rec_nn(REC_FEAT, REC_SERVICES)


def _recommend_meta_model(plan):
    """The LOCAL-head recommender FedMeta ships: same trunk, but a
    ``local_head``-way output over the client's own services — the θ-size
    asymmetry behind the paper's Table-3 bytes advantage."""
    from repro.models.paper import rec_nn
    return rec_nn(REC_FEAT, plan.local_head or REC_HEAD)


def _recommend_meta_data(clients, plan):
    from repro.data import localize_clients
    return localize_clients(clients, plan.local_head or REC_HEAD)


def _recommend_loss(model):
    from repro.core import classification_loss
    return classification_loss(model.apply, topk=(4,))   # Table 3: Top-1/Top-4


# ---- LM personalization scenario ----------------------------------------
# Per-client dialect corpora (data/lm_tasks.make_lm_clients) on a reduced
# assigned LM architecture — small vocab/seq so the path runs in CI.
LM_VOCAB, LM_SEQ = 64, 16


def _lm_data(num_clients, seed, **lazy_kw):
    from repro.data import make_lm_clients
    return make_lm_clients(num_clients=num_clients, seq_len=LM_SEQ,
                           vocab=LM_VOCAB, seed=seed, **lazy_kw)


def _lm_model():
    import dataclasses as dc

    from repro.configs import get_config, reduced_config
    from repro.launch.steps import make_apply_fn
    from repro.models import init_lm
    from repro.models.paper import Model
    cfg = dc.replace(reduced_config(get_config("smollm-360m")),
                     num_layers=2, d_model=64, num_heads=2, num_kv_heads=1,
                     head_dim=32, d_ff=128, vocab_size=LM_VOCAB,
                     dtype="float32")
    return Model(lambda key: init_lm(key, cfg), make_apply_fn(cfg),
                 f"lm-{cfg.name}")


def _lm_loss(model):
    from repro.core import lm_pair_loss
    return lm_pair_loss(model.apply)


# dataset name -> builders + paper-Table-4-shaped hyperparameters
# (CPU-scaled). Like the paper's Table 4, learning rates may be tuned
# per algorithm (method_overrides) — the sharing discipline is about
# data splits, sampling streams, and comm accounting, not about forcing
# one lr onto algorithms with different update geometries.
#
# Scenario extension points (all optional; DESIGN.md §13):
#   loss        loss(model) -> (loss_fn, eval_fn); default
#               classification_loss(model.apply)
#   meta_model  meta_model(plan) -> Model for the FedMeta methods (the
#               baselines keep `model`) — the recommend local head
#   meta_data   meta_data(clients, plan) -> clients view the FedMeta
#               methods train/eval on (order- and size-preserving)
#   support_frac / local_head   extra per-dataset plan defaults
DATASETS = {
    "femnist": dict(data=_femnist_data, model=_femnist_model,
                    inner_lr=0.01, outer_lr=1e-3, local_lr=1e-3,
                    clients_per_round=4, support_size=16, query_size=16,
                    num_clients=100,
                    # first-order MAML stagnates at inner_lr=0.01 on
                    # synthetic femnist; 0.05 converges (probed in PR 3)
                    method_overrides={"fomaml": {"inner_lr": 0.05}}),
    "sent140": dict(data=_sent140_data, model=_sent140_model,
                    inner_lr=0.01, outer_lr=1e-3, local_lr=1e-3,
                    clients_per_round=8, support_size=16, query_size=16,
                    num_clients=100),
    "shakespeare": dict(data=_shakespeare_data, model=_shakespeare_model,
                        inner_lr=0.1, outer_lr=1e-2, local_lr=1e-3,
                        clients_per_round=8, support_size=24, query_size=24,
                        num_clients=48),
    "recommend": dict(data=_recommend_data, model=_recommend_model,
                      loss=_recommend_loss, meta_model=_recommend_meta_model,
                      meta_data=_recommend_meta_data,
                      # the local head's label semantics are per-client
                      # (local id 0 = the client's first service), so
                      # META models lean on real local adaptation — the
                      # paper trains them with 100 local steps; 5 inner
                      # steps at lr 0.1 is the CPU-scaled analogue
                      # (probed: 1 step 0.11, 5 steps 0.24 test acc vs
                      # FedAvg 0.046)
                      inner_lr=0.1, inner_steps=5,
                      outer_lr=1e-3, local_lr=1e-3,
                      clients_per_round=8, support_size=32, query_size=16,
                      num_clients=120, support_frac=0.5,
                      local_head=REC_HEAD),
    "lm": dict(data=_lm_data, model=_lm_model, loss=_lm_loss,
               inner_lr=0.1, outer_lr=3e-3, local_lr=1e-2,
               clients_per_round=4, support_size=4, query_size=4,
               num_clients=32, support_frac=0.5),
}


@dataclasses.dataclass
class ExperimentPlan:
    """Everything needed to reproduce one FedMeta-vs-FedAvg comparison.

    ``pipeline`` selects the FedMeta execution substrate: "tree" (pytree
    φ, the reference) or "client_plane" (the flat pipeline: φ, the inner
    loop and the gradient block on flat memory) — the baselines are
    substrate-independent.
    ``data_fn(num_clients, seed)`` / ``model_fn()`` / ``loss_builder
    (model)`` / ``meta_model_fn(plan)`` / ``meta_data_fn(clients, plan)``
    override the named registry for custom scenarios (callables are not
    serialized). ``local_head`` is the FedMeta head width for scenarios
    with a per-method model-size asymmetry (recommend: 40, the paper's
    §4.3 local classifier; None = no asymmetry).

    Example — the committed recommend artifact's plan::

        plan = default_plan("recommend", rounds=60, eval_every=2)
        out = run_comparison(plan, log=print)
        print(format_table(out))
    """
    dataset: str
    methods: Sequence[str] = DEFAULT_METHODS
    rounds: int = 100
    eval_every: int = 10
    num_clients: int = 100
    clients_per_round: int = 4
    support_frac: float = 0.2
    support_size: int = 16
    query_size: int = 16
    inner_lr: float = 0.01
    inner_steps: int = 1           # FedMeta inner-loop steps (adapt + train)
    outer_lr: float = 1e-3
    local_lr: float = 1e-3
    local_steps: int = 3
    target_acc: Optional[float] = None   # None = shared reachable target
    # a target counts as reached only when held for this many
    # consecutive evals — single-eval noise spikes must not set the
    # comm-to-target table (charged at the window's last round)
    sustain_evals: int = 2
    pipeline: str = "tree"               # tree | client_plane
    client_chunk: Optional[int] = None
    # async round engine (DESIGN.md §12): staged round blocks ahead of
    # the device (0 = the synchronous loop) and the deferred-metrics
    # flush cadence. Bit-identity of the pipelined loop means the
    # comparison artifacts regenerate unchanged at any depth — the
    # depth-0 invariant is pinned by test_experiment_plane.
    prefetch_depth: int = 0
    flush_every: int = 1
    fuse_rounds: int = 1                 # lax.scan round blocks (packed)
    # failure plane (DESIGN.md §14): FedMeta (m, N) aggregation mode and
    # optional per-round client-failure injection. Applies to the
    # FedMeta methods only (the FedAvg baselines have no (m, N) gradient
    # plane); requires pipeline="client_plane". The faults
    # config is a frozen dataclass and serializes into the artifact, so
    # a robustness sweep's JSON records its exact failure model.
    aggregator: str = "mean"             # mean|masked_mean|screen|trimmed
    screen_factor: float = 3.0
    trim: int = 1
    faults: Optional["FaultConfig"] = None
    # population plane (DESIGN.md §15): lazy client registry +
    # deadline/over-selection staging. ``lazy_population`` builds the
    # dataset as a bounded-memory `ClientRegistry` (sequential mode is
    # bit-identical to eager; ``independent_population=True`` switches
    # to O(1) per-client seeding for 10^5+ populations).
    # ``eval_clients_cap`` bounds the val/test cohorts — at population
    # scale "evaluate on all test clients" is neither feasible nor
    # meaningful. The unreliability/deadline/over-selection knobs apply
    # to the FedMeta methods only (like faults: they need the (m, N)
    # gradient plane).
    lazy_population: bool = False
    independent_population: bool = False
    cache_clients: Optional[int] = None
    eval_clients_cap: Optional[int] = None
    over_select: float = 0.0
    round_deadline: Optional[float] = None
    unreliability: Optional["UnreliabilityConfig"] = None
    pool_workers: int = 0
    # bytes-on-the-wire plane (DESIGN.md §17): upload compression +
    # central DP for the FedMeta methods (they need the (m, N) gradient
    # plane, like faults — pipeline="client_plane" only; the
    # FedAvg baselines ship dense full models by construction).
    # ``block_dtype``/``opt_state_dtype`` are dtype NAMES ("bfloat16")
    # so plans stay JSON-serializable: the gradient-block wire dtype
    # and the fused-Adam m/v state dtype (None = float32 for both).
    compression: Optional["CompressionConfig"] = None
    dp: Optional["DPConfig"] = None
    block_dtype: Optional[str] = None
    opt_state_dtype: Optional[str] = None
    # FedMeta head width for local-head scenarios (DESIGN.md §13)
    local_head: Optional[int] = None
    # per-method lr/step overrides, paper-Table-4 style:
    # {"fomaml": {"inner_lr": 0.05}}
    method_overrides: dict = dataclasses.field(default_factory=dict)
    seed: int = 0
    name: str = ""
    data_fn: Optional[Callable] = None
    model_fn: Optional[Callable] = None
    loss_builder: Optional[Callable] = None
    meta_model_fn: Optional[Callable] = None
    meta_data_fn: Optional[Callable] = None

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        for fn in ("data_fn", "model_fn", "loss_builder", "meta_model_fn",
                   "meta_data_fn"):
            d.pop(fn)
        d["methods"] = list(self.methods)
        return d


def default_plan(dataset: str, **overrides) -> ExperimentPlan:
    """Plan with the registry hyperparameters for a named dataset.

    >>> plan = default_plan("recommend", rounds=8, eval_every=2)
    >>> plan.local_head, plan.clients_per_round
    (40, 8)
    """
    su = DATASETS[dataset]
    base = dict(clients_per_round=su["clients_per_round"],
                support_size=su["support_size"],
                query_size=su["query_size"], inner_lr=su["inner_lr"],
                outer_lr=su["outer_lr"], local_lr=su["local_lr"],
                num_clients=su["num_clients"],
                # copy: plans must not alias (and mutate) the registry
                method_overrides={k: dict(v) for k, v in
                                  su.get("method_overrides", {}).items()})
    for opt in ("support_frac", "local_head", "inner_steps"):
        if opt in su:
            base[opt] = su[opt]
    base.update(overrides)
    return ExperimentPlan(dataset=dataset, **base)


def make_trainer(plan: ExperimentPlan, method: str, loss_fn, eval_fn,
                 train_clients):
    """One trainer per method, all sharing plan-level sampling config.

    FedAvg methods get a `FedAvgTrainer` (full-model shipping), FedMeta
    methods a `FederatedTrainer` on the plan's pipeline; `method_overrides`
    apply per method. Example::

        tr = make_trainer(plan, "fomaml", loss_fn, eval_fn, train_clients)
        state = tr.init(jax.random.PRNGKey(0), model.init)
        state = tr.run(state, plan.rounds, eval_every=plan.eval_every,
                       eval_clients=val_clients)
    """
    if plan.pipeline not in PIPELINES:
        raise ValueError(f"unknown pipeline {plan.pipeline!r}; expected "
                         f"one of {PIPELINES}")
    common = dict(clients_per_round=plan.clients_per_round,
                  support_frac=plan.support_frac,
                  support_size=plan.support_size,
                  query_size=plan.query_size, seed=plan.seed,
                  prefetch_depth=plan.prefetch_depth,
                  flush_every=plan.flush_every)
    over = plan.method_overrides.get(method, {})
    if method in FEDAVG_METHODS:
        return FedAvgTrainer(
            loss_fn, eval_fn,
            local_lr=over.get("local_lr", plan.local_lr),
            local_steps=over.get("local_steps", plan.local_steps),
            train_clients=train_clients, client_chunk=plan.client_chunk,
            meta_eval=(method == "fedavg(meta)"), **common)
    from repro.core import make_algorithm
    from repro.optim import adam
    algo = make_algorithm(method, loss_fn, eval_fn,
                          inner_lr=over.get("inner_lr", plan.inner_lr),
                          inner_steps=over.get("inner_steps",
                                               plan.inner_steps))
    import jax.numpy as jnp
    opt_kw = {}
    if plan.opt_state_dtype:
        # quantized optimizer state (§17): fused Adam keeps m/v in this
        # dtype and dequantizes inside the kernel (the olmax trick)
        opt_kw["state_dtype"] = jnp.dtype(plan.opt_state_dtype)
    return FederatedTrainer(
        algo, adam(over.get("outer_lr", plan.outer_lr), **opt_kw),
        train_clients,
        client_axis="chunked" if plan.client_chunk else "vmap",
        client_chunk=plan.client_chunk,
        packed=(plan.pipeline == "client_plane"),
        block_dtype=(jnp.dtype(plan.block_dtype)
                     if plan.block_dtype else None),
        compression=plan.compression, dp=plan.dp,
        fuse_rounds=plan.fuse_rounds, aggregator=plan.aggregator,
        screen_factor=plan.screen_factor, trim=plan.trim, faults=plan.faults,
        unreliability=plan.unreliability, over_select=plan.over_select,
        round_deadline=plan.round_deadline, pool_workers=plan.pool_workers,
        **common)


@dataclasses.dataclass
class _View:
    """One method family's view of the scenario: the client splits it
    trains/evals on plus the model and loss that go with them."""
    train: list
    val: list
    test: list
    model: object
    loss_fn: Callable
    eval_fn: Callable


def _build_views(plan: ExperimentPlan, su: dict):
    """-> (global_view, meta_view): identical unless the scenario defines
    a per-method asymmetry (meta_model / meta_data), in which case the
    FedMeta methods get their own model and client-data view while the
    baselines keep the global one. Views preserve client order and sizes,
    so both consume identical seeded sampling streams."""
    from repro.core import classification_loss
    data_fn = plan.data_fn or su["data"]
    model_fn = plan.model_fn or su["model"]
    loss_builder = plan.loss_builder or su.get("loss") or (
        lambda model: classification_loss(model.apply))
    lazy_kw = {}
    if plan.lazy_population:
        # registry datasets: the builders forward these to make_*; a
        # custom plan.data_fn must accept the same keywords
        lazy_kw = dict(lazy=True, independent=plan.independent_population,
                       cache_clients=plan.cache_clients)
    ds = data_fn(plan.num_clients, plan.seed, **lazy_kw)
    train, val, test = ds.split_clients(seed=plan.seed)
    model = model_fn()
    gview = _View(train, val, test, model, *loss_builder(model))

    meta_model_fn = plan.meta_model_fn or su.get("meta_model")
    meta_data_fn = plan.meta_data_fn or su.get("meta_data")
    if meta_model_fn is None and meta_data_fn is None:
        return gview, gview
    mmodel = meta_model_fn(plan) if meta_model_fn else model
    if meta_data_fn:
        # eager scenarios return lists; lazy ones a RegistryView — both
        # satisfy the Sequence contract, so neither is re-materialized
        mtrain, mval, mtest = (meta_data_fn(c, plan)
                               for c in (train, val, test))
    else:
        mtrain, mval, mtest = train, val, test
    return gview, _View(mtrain, mval, mtest, mmodel,
                        *loss_builder(mmodel))


def _cap_clients(clients, cap: Optional[int]):
    """Bound an eval cohort (population scale): both lists and
    `RegistryView`s slice to a prefix view without materializing."""
    return clients[:cap] if cap and len(clients) > cap else clients


def _eval_records(history: list) -> list:
    return [rec for rec in history if rec.get("eval_acc") is not None]


def comm_to_target(history: list, target_acc: float,
                   sustain: int = 1) -> Optional[dict]:
    """The paper's Fig.-3 metric: cumulative communication (and client
    compute) to reach ``target_acc`` on held-out clients.

    With ``sustain=k`` the target must hold on k consecutive evals and
    the cost is charged at the LAST round of the first such window — a
    single noisy eval spike cannot set the table. History records carry
    cumulative comm fields, so the result is monotone in the target: a
    higher target can only cost more bytes. Returns None when the
    target is never (sustainably) reached.

    >>> hist = [{"round": r, "eval_acc": 0.1 * r, "comm_MB": 2.0 * r,
    ...          "upload_MB": r, "download_MB": r, "client_GFLOPs": 0.0}
    ...         for r in (1, 2, 3)]
    >>> comm_to_target(hist, 0.2)["rounds"]
    2
    """
    evals = _eval_records(history)
    k = max(1, min(sustain, len(evals)))
    for i in range(len(evals) - k + 1):
        window = evals[i:i + k]
        if all(rec["eval_acc"] >= target_acc for rec in window):
            rec = window[-1]
            return {"rounds": rec["round"], "comm_MB": rec["comm_MB"],
                    "upload_MB": rec["upload_MB"],
                    "download_MB": rec["download_MB"],
                    "client_GFLOPs": rec["client_GFLOPs"],
                    "eval_acc": rec["eval_acc"]}
    return None


def fairness_stats(per_client) -> dict:
    """Accuracy-distribution (fairness) summary across clients, after
    Li et al.'s federated-learning survey: deciles, variance, and the
    mean over the worst-off 10% of clients. A method can buy mean
    accuracy by abandoning its tail; these fields make that visible in
    every comparison artifact.

    Pure function of the per-client accuracies, so committed artifacts
    can be re-derived exactly (test_scenario_plane pins this).

    >>> fairness_stats([1.0, 0.0])["worst10_mean"]
    0.0
    """
    import numpy as np
    a = np.sort(np.asarray(per_client, np.float64))
    k = max(1, int(np.ceil(0.1 * len(a))))
    return {
        "mean": float(a.mean()),
        "variance": float(a.var()),
        "deciles": [float(np.percentile(a, p)) for p in range(10, 100, 10)],
        "worst10_mean": float(a[:k].mean()),
        "num_clients": int(len(a)),
    }


def _sustained_best(history: list, sustain: int) -> Optional[float]:
    """Best accuracy the method HELD for ``sustain`` consecutive evals
    (the max over windows of the window min)."""
    evals = [rec["eval_acc"] for rec in _eval_records(history)]
    if not evals:
        return None
    k = max(1, min(sustain, len(evals)))
    return max(min(evals[i:i + k]) for i in range(len(evals) - k + 1))


def _shared_target(results: dict, sustain: int) -> Optional[float]:
    """Highest accuracy every method sustainably reached — the natural
    shared target when the plan does not pin one. Derived under the
    same sustain rule as `comm_to_target`, so every row of the table is
    finite and comparable by construction."""
    best = []
    for r in results.values():
        b = _sustained_best(r["history"], sustain)
        if b is None:
            return None
        best.append(b)
    return min(best) if best else None


def run_comparison(plan: ExperimentPlan, out_dir: str = "results/experiments",
                   log: Callable = None, save: bool = True) -> dict:
    """Run every plan method on the shared split/stream; return (and
    optionally write) the full comparison record.

    The record's schema is documented field-by-field in DESIGN.md §13;
    the JSON artifact lands at ``{out_dir}/{name or dataset}_compare.json``.
    Example::

        out = run_comparison(default_plan("sent140", rounds=60), log=print)
        print(format_table(out))              # comm-to-target table
        out["methods"]["maml"]["fairness"]    # per-client acc distribution
    """
    say = log or (lambda *a, **k: None)
    su = DATASETS.get(plan.dataset, {})
    gview, mview = _build_views(plan, su)

    results = {}
    for method in plan.methods:
        view = gview if method in FEDAVG_METHODS else mview
        val = _cap_clients(view.val, plan.eval_clients_cap)
        test = _cap_clients(view.test, plan.eval_clients_cap)
        tr = make_trainer(plan, method, view.loss_fn, view.eval_fn,
                          view.train)
        state = tr.init(jax.random.PRNGKey(plan.seed), view.model.init)
        tr.measure_flops(state)
        # perf_counter, not time.time: interval timing is the only
        # wall-clock this module is allowed (det-wallclock invariant)
        t0 = time.perf_counter()
        state = tr.run(state, plan.rounds, eval_every=plan.eval_every,
                       eval_clients=val)
        seconds = time.perf_counter() - t0
        # reuse the trainer's jitted evaluator — a fresh one would
        # recompile the whole adapt+eval graph for the test pass
        if method in FEDAVG_METHODS:
            test_acc, per_client, test_loss = evaluate_global(
                view.eval_fn, state["theta"], test,
                support_frac=plan.support_frac,
                support_size=plan.support_size, query_size=plan.query_size,
                seed=plan.seed, evaluator=tr.evaluator())
        else:
            test_acc, per_client, test_loss = evaluate_meta(
                tr.algo, tr.phi_tree(state), test,
                support_frac=plan.support_frac,
                support_size=plan.support_size, query_size=plan.query_size,
                seed=plan.seed, evaluator=tr.evaluator())
        results[method] = {
            "history": tr.history,
            "test_acc": test_acc, "test_loss": test_loss,
            "per_client": [float(a) for a in per_client],
            "fairness": fairness_stats(per_client),
            "comm": tr.comm.summary(), "seconds": seconds,
        }
        if sanitizers_enabled():
            # invariant plane (DESIGN.md §16): everything entering the
            # artifact must be host data — a tracer here means a jitted
            # step leaked an abstract value into history
            assert_no_tracers(results[method],
                              where=f"{plan.dataset}/{method} record")
        say(f"[{plan.dataset}] {method}: test_acc={test_acc:.4f} "
            f"comm_MB={tr.comm.summary()['comm_MB']:.2f} "
            f"phi_MB={tr.comm.summary()['phi_MB']:.4f} ({seconds:.0f}s)")

    target = plan.target_acc if plan.target_acc is not None \
        else _shared_target(results, plan.sustain_evals)
    table = {}
    if target is not None:
        table = {m: comm_to_target(r["history"], target,
                                   sustain=plan.sustain_evals)
                 for m, r in results.items()}
        base = table.get("fedavg")
        # FedAvg never (sustainably) reaching the target is itself the
        # paper's claim — reductions then use its FULL-RUN spend and are
        # lower bounds (it would need at least that much)
        if base is not None:
            base_mb, bound = base["comm_MB"], False
        elif "fedavg" in results:
            base_mb, bound = results["fedavg"]["comm"]["comm_MB"], True
        else:
            base_mb, bound = None, False
        for m, row in table.items():
            if row and base_mb and row["comm_MB"]:
                row["comm_reduction_vs_fedavg"] = round(
                    base_mb / row["comm_MB"], 2)
                if bound:
                    row["comm_reduction_is_lower_bound"] = True

    out = {"plan": plan.to_json(), "target_acc": target,
           "comm_to_target": table,
           "methods": {m: {k: v for k, v in r.items() if k != "per_client"}
                       for m, r in results.items()},
           "per_client": {m: r["per_client"] for m, r in results.items()}}
    if save:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(
            out_dir, f"{plan.name or plan.dataset}_compare.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
        out["path"] = path
        say(f"[{plan.dataset}] wrote {path} (target_acc={target})")
    return out


def format_table(out: dict) -> str:
    """Human-readable comm-to-target table for one comparison record.

    >>> print(format_table(run_comparison(plan, save=False)))  # doctest: +SKIP
    target accuracy: 0.7
    method         rounds   comm_MB    up_MB  down_MB   GFLOPs test_acc vs_fedavg
    ...
    """
    lines = [f"target accuracy: {out['target_acc']}",
             f"{'method':<14} {'rounds':>6} {'comm_MB':>9} {'up_MB':>8} "
             f"{'down_MB':>8} {'GFLOPs':>8} {'test_acc':>8} {'vs_fedavg':>9}"]
    for m, res in out["methods"].items():
        row = (out.get("comm_to_target") or {}).get(m)
        if row:
            red = row.get("comm_reduction_vs_fedavg", "")
            if red and row.get("comm_reduction_is_lower_bound"):
                red = f">={red}"
            lines.append(
                f"{m:<14} {row['rounds']:>6} {row['comm_MB']:>9.2f} "
                f"{row['upload_MB']:>8.2f} {row['download_MB']:>8.2f} "
                f"{row['client_GFLOPs']:>8.2f} {res['test_acc']:>8.4f} "
                f"{red:>9}")
        else:
            lines.append(f"{m:<14} {'—':>6} {'—':>9} {'—':>8} {'—':>8} "
                         f"{'—':>8} {res['test_acc']:>8.4f} {'—':>9}")
    return "\n".join(lines)
