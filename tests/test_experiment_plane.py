"""Experiment plane + FedAvg parity + evaluation-path regression tests.

Covers the PR-3 invariants: FedAvg round-loop comm accounting
(upload bytes = m * bytes(θ) * rounds — full model both ways), the
query-count-weighted §4.1 evaluation vs hand-computed values, the
packed-trainer example path (phi_tree, never state["phi"]), per-step
finetune minibatches, per-round history, and comm-to-target-accuracy
monotonicity.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import classification_loss, make_algorithm
from repro.data.federated import (ClientData, FederatedDataset,
                                  sample_task_batch)
from repro.federated.comm import CommTracker
from repro.federated.experiment import (ExperimentPlan, comm_to_target,
                                        make_trainer, run_comparison)
from repro.federated.fedavg import FedAvgTrainer
from repro.federated.server import (FederatedTrainer, evaluate_global,
                                    evaluate_meta)
from repro.optim import adam
from repro.utils.pytree import tree_bytes


def _tiny_dataset(num_clients=12, seed=0, feat=4, classes=2):
    rng = np.random.RandomState(seed)
    mu = rng.normal(0, 1, (classes, feat))
    clients = []
    for _ in range(num_clients):
        n = rng.randint(10, 24)
        y = rng.randint(0, classes, (n,))
        x = mu[y] + rng.normal(0, 0.3, (n, feat))
        clients.append(ClientData(x.astype(np.float32), y.astype(np.int64)))
    return FederatedDataset(clients, num_classes=classes, name="tiny")


class _TinyModel:
    name = "tiny-linear"

    @staticmethod
    def init(key):
        k, _ = jax.random.split(key)
        return {"w": jax.random.normal(k, (4, 2)) * 0.1,
                "b": jnp.zeros((2,))}

    @staticmethod
    def apply(params, x):
        return x @ params["w"] + params["b"]


def _loss_eval():
    return classification_loss(_TinyModel.apply)


def _fedavg(ds, **kw):
    loss_fn, eval_fn = _loss_eval()
    args = dict(local_lr=0.05, local_steps=3, train_clients=ds.clients,
                clients_per_round=4, support_frac=0.5, support_size=8,
                query_size=8, seed=0)
    args.update(kw)
    return FedAvgTrainer(loss_fn, eval_fn, **args)


# ---- FedAvg round loop + comm accounting --------------------------------

def test_fedavg_run_comm_invariants():
    ds = _tiny_dataset()
    fa = _fedavg(ds)
    state = fa.init(jax.random.PRNGKey(0), _TinyModel.init)
    rounds = 5
    state = fa.run(state, rounds, eval_every=2, eval_clients=ds.clients[:4])
    theta_bytes = tree_bytes(state["theta"])
    m = fa.clients_per_round
    # FedAvg ships the FULL model both ways every round
    assert fa.comm.upload_bytes == rounds * m * theta_bytes
    assert fa.comm.download_bytes == rounds * m * theta_bytes
    assert fa.comm.total_bytes == 2 * rounds * m * theta_bytes
    # history: one record per round, eval fields only on eval rounds
    assert len(fa.history) == rounds
    assert [r["round"] for r in fa.history] == [1, 2, 3, 4, 5]
    assert all("train_loss" in r and "accuracy" in r for r in fa.history)
    eval_rounds = [r["round"] for r in fa.history if "eval_acc" in r]
    assert eval_rounds == [2, 4, 5]
    # cumulative comm recorded per round
    comms = [r["comm_MB"] for r in fa.history]
    assert all(b > a for a, b in zip(comms, comms[1:]))
    assert fa.history[-1]["upload_MB"] == pytest.approx(
        fa.comm.upload_bytes / 1e6)


def test_fedavg_chunked_matches_vmap():
    ds = _tiny_dataset()
    loss_fn, eval_fn = _loss_eval()
    theta = _TinyModel.init(jax.random.PRNGKey(1))
    rng = np.random.RandomState(3)
    tb = sample_task_batch(ds.clients, 6, 0.5, 8, 8, rng)
    batches = (jnp.asarray(np.stack([tb.support_x] * 2, axis=1)),
               jnp.asarray(np.stack([tb.support_y] * 2, axis=1)))
    w = jnp.asarray(tb.weight)
    full = _fedavg(ds).round_step({"theta": theta}, batches, w)
    # chunk that does NOT divide m=6 exercises zero-weight padding
    chunked = _fedavg(ds, client_chunk=4).round_step(
        {"theta": theta}, batches, w)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_fedavg_weighted_aggregation():
    """weights concentrate on client 0 -> the round returns client 0's
    locally trained model, not the uniform average."""
    ds = _tiny_dataset()
    fa = _fedavg(ds)
    theta = _TinyModel.init(jax.random.PRNGKey(1))
    rng = np.random.RandomState(3)
    tb = sample_task_batch(ds.clients, 3, 0.5, 8, 8, rng)
    batches = (jnp.asarray(tb.support_x[:, None]),
               jnp.asarray(tb.support_y[:, None]))
    w = jnp.asarray([1.0, 0.0, 0.0])
    out = fa.round_step({"theta": theta}, batches, w)["theta"]
    solo = fa.local_train(theta, jax.tree.map(lambda x: x[0], batches))
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(solo)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ---- §4.1 query-count-weighted evaluation -------------------------------

def test_weighted_eval_vs_hand_computed():
    """Clients with constant labels and known sizes: the fake evaluator
    'predicts' each client's constant label as its accuracy, so the
    §4.1 accuracy must equal sum(n_q * acc) / sum(n_q) regardless of
    the (random) client order in the eval batch."""
    sizes_labels = [(10, 1), (4, 0), (6, 1)]   # support_frac=0.5
    clients = [ClientData(np.zeros((n, 4), np.float32),
                          np.full((n,), lab, np.int64))
               for n, lab in sizes_labels]
    # n_sup = round(0.5*n) -> query counts 5, 2, 3
    expect_acc = (5 * 1 + 2 * 0 + 3 * 1) / (5 + 2 + 3)       # 0.8
    unweighted = (1 + 0 + 1) / 3

    def fake_evaluator(_params, support, query):
        accs = jnp.mean(query[1].astype(jnp.float32), axis=1)
        return accs, 1.0 - accs    # loss complements acc

    loss_fn, eval_fn = _loss_eval()
    acc, per_client, loss = evaluate_global(
        eval_fn, {"w": jnp.zeros((4, 2))}, clients, support_frac=0.5,
        support_size=4, query_size=4, seed=0, evaluator=fake_evaluator)
    assert acc == pytest.approx(expect_acc)
    assert acc != pytest.approx(unweighted)
    assert loss == pytest.approx(1.0 - expect_acc)
    assert sorted(per_client.tolist()) == [0.0, 1.0, 1.0]

    algo = make_algorithm("fomaml", loss_fn, eval_fn, inner_lr=0.05)
    acc_m, _, loss_m = evaluate_meta(
        algo, {"theta": None}, clients, support_frac=0.5, support_size=4,
        query_size=4, seed=0, evaluator=fake_evaluator)
    assert acc_m == pytest.approx(expect_acc)
    assert loss_m == pytest.approx(1.0 - expect_acc)


def test_task_batch_query_counts():
    ds = _tiny_dataset()
    rng = np.random.RandomState(0)
    tb = sample_task_batch(ds.clients, 4, 0.5, 8, 8, rng)
    assert tb.query_count is not None and tb.query_count.shape == (4,)
    assert (tb.query_count >= 1).all()
    # counts are the TRUE query sizes, not the resampled fixed shape
    ns = sorted(c.n for c in ds.clients)
    assert tb.query_count.max() <= ns[-1]


# ---- finetune: per-step seeded minibatches ------------------------------

def test_finetune_per_step_minibatches():
    ds = _tiny_dataset()
    fa = _fedavg(ds, local_optimizer="sgd", local_lr=0.1,
                 finetune_batch_size=4)
    theta = _TinyModel.init(jax.random.PRNGKey(2))
    rng = np.random.RandomState(1)
    tb = sample_task_batch(ds.clients, 1, 0.5, 8, 8, rng)
    support = (jnp.asarray(tb.support_x[0]), jnp.asarray(tb.support_y[0]))
    a = fa.finetune(theta, support, steps=3)
    b = fa.finetune(theta, support, steps=3)
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    # and it is NOT the old broadcast-one-batch behavior
    broadcast = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (3,) + x.shape), support)
    old = fa.local_train(theta, broadcast)
    assert any(not np.allclose(np.asarray(la), np.asarray(lo))
               for la, lo in zip(jax.tree.leaves(a), jax.tree.leaves(old)))


# ---- packed-trainer example path ----------------------------------------

def test_packed_trainer_example_path():
    ds = _tiny_dataset(num_clients=10)
    loss_fn, eval_fn = _loss_eval()
    algo = make_algorithm("fomaml", loss_fn, eval_fn, inner_lr=0.05)
    tr = FederatedTrainer(algo, adam(0.01), ds.clients[:6],
                          clients_per_round=3, support_frac=0.5,
                          support_size=8, query_size=8, packed=True)
    state = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
    state = tr.run(state, 2, eval_every=1, eval_clients=ds.clients[6:])
    # state["phi"] is a FLAT buffer on the packed pipeline...
    assert jnp.ndim(state["phi"]) == 1
    # ...and phi_tree is the example-facing accessor that always works
    acc, per_client, loss = evaluate_meta(
        algo, tr.phi_tree(state), ds.clients[6:], support_frac=0.5,
        support_size=8, query_size=8)
    assert 0.0 <= acc <= 1.0 and np.isfinite(loss)
    assert len(tr.history) == 2
    assert all("eval_acc" in r for r in tr.history)


def test_federated_trainer_history_every_round():
    ds = _tiny_dataset(num_clients=10)
    loss_fn, eval_fn = _loss_eval()
    algo = make_algorithm("fomaml", loss_fn, eval_fn, inner_lr=0.05)
    tr = FederatedTrainer(algo, adam(0.01), ds.clients[:6],
                          clients_per_round=3, support_frac=0.5,
                          support_size=8, query_size=8)
    state = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
    tr.run(state, 5, eval_every=3, eval_clients=ds.clients[6:])
    assert [r["round"] for r in tr.history] == [1, 2, 3, 4, 5]
    assert all("query_loss" in r and "comm_MB" in r for r in tr.history)
    assert [r["round"] for r in tr.history if "eval_acc" in r] == [3, 5]


# ---- comm-to-target metric ----------------------------------------------

def _mk_history(accs, mb_per_round=2.0):
    hist = []
    for i, acc in enumerate(accs):
        rec = {"round": i + 1, "comm_MB": mb_per_round * (i + 1),
               "upload_MB": mb_per_round * (i + 1) / 2,
               "download_MB": mb_per_round * (i + 1) / 2,
               "client_GFLOPs": 0.1 * (i + 1)}
        if acc is not None:
            rec["eval_acc"] = acc
        hist.append(rec)
    return hist


def test_comm_to_target_monotone_in_target():
    hist = _mk_history([None, 0.3, None, 0.5, None, 0.7])
    rows = [comm_to_target(hist, t) for t in (0.1, 0.3, 0.4, 0.5, 0.69)]
    assert all(r is not None for r in rows)
    mbs = [r["comm_MB"] for r in rows]
    assert all(b >= a for a, b in zip(mbs, mbs[1:]))
    assert comm_to_target(hist, 0.71) is None
    assert comm_to_target(hist, 0.3)["rounds"] == 2


def test_comm_to_target_uses_first_crossing():
    hist = _mk_history([0.2, 0.6, 0.4, 0.8])
    assert comm_to_target(hist, 0.5)["rounds"] == 2


def test_comm_to_target_sustained_ignores_noise_spike():
    hist = _mk_history([0.2, 0.6, 0.4, 0.7, 0.8])
    # a single noisy 0.6 eval must not count with sustain=2; the first
    # window holding >= 0.5 is rounds (4, 5), charged at its last round
    assert comm_to_target(hist, 0.5, sustain=2)["rounds"] == 5
    assert comm_to_target(hist, 0.75, sustain=2) is None
    # sustain larger than the eval list degrades to min over all evals
    assert comm_to_target(hist, 0.1, sustain=99)["rounds"] == 5

    from repro.federated.experiment import _sustained_best
    assert _sustained_best(hist, 1) == 0.8
    assert _sustained_best(hist, 2) == pytest.approx(0.7)


def test_method_overrides():
    from repro.federated.experiment import make_trainer
    loss_fn, eval_fn = _loss_eval()
    ds = _tiny_dataset()
    plan = ExperimentPlan(
        dataset="tiny", inner_lr=0.1, local_steps=2,
        method_overrides={"fomaml": {"inner_lr": 0.05},
                          "fedavg": {"local_steps": 7}},
        data_fn=lambda n, s: _tiny_dataset(n, s), model_fn=lambda: _TinyModel)
    assert make_trainer(plan, "fomaml", loss_fn, eval_fn,
                        ds.clients).algo.inner_lr == 0.05
    assert make_trainer(plan, "maml", loss_fn, eval_fn,
                        ds.clients).algo.inner_lr == 0.1
    assert make_trainer(plan, "fedavg", loss_fn, eval_fn,
                        ds.clients).local_steps == 7
    assert plan.to_json()["method_overrides"] == plan.method_overrides


def test_shared_sampling_stream_parity(monkeypatch):
    """The experiment plane's core invariant: FederatedTrainer and
    FedAvgTrainer under the same seed consume IDENTICAL task-sampling
    streams — same clients, same support/query splits, every round.
    Both run() loops draw through the shared TaskStream
    (data.federated), so that call site is patched for the round draws;
    measure_flops still draws directly from each trainer module."""
    import repro.data.federated as dfed
    import repro.federated.fedavg as fav
    import repro.federated.server as srv
    from repro.data.federated import sample_task_batch as real

    logs = {"meta": [], "avg": []}

    def recorder(key):
        def wrapped(clients, m, *a, **kw):
            tb = real(clients, m, *a, **kw)
            logs[key].append((np.asarray(tb.support_x).tobytes(),
                              np.asarray(tb.query_x).tobytes(),
                              np.asarray(tb.weight).tobytes()))
            return tb
        return wrapped

    ds = _tiny_dataset()
    loss_fn, eval_fn = _loss_eval()
    common = dict(clients_per_round=4, support_frac=0.5, support_size=8,
                  query_size=8, seed=7)

    monkeypatch.setattr(srv, "sample_task_batch", recorder("meta"))
    monkeypatch.setattr(dfed, "sample_task_batch", recorder("meta"))
    algo = make_algorithm("fomaml", loss_fn, eval_fn, inner_lr=0.05)
    tr = FederatedTrainer(algo, adam(0.01), ds.clients, **common)
    st = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
    tr.measure_flops(st)
    tr.run(st, 3)

    monkeypatch.setattr(fav, "sample_task_batch", recorder("avg"))
    monkeypatch.setattr(dfed, "sample_task_batch", recorder("avg"))
    fa = FedAvgTrainer(loss_fn, eval_fn, local_lr=0.05,
                       train_clients=ds.clients, **common)
    st = fa.init(jax.random.PRNGKey(0), _TinyModel.init)
    fa.measure_flops(st)
    fa.run(st, 3)

    assert len(logs["meta"]) == len(logs["avg"]) == 4  # flops probe + 3
    assert logs["meta"] == logs["avg"]


# ---- full comparison smoke ----------------------------------------------

def test_run_comparison_smoke(tmp_path):
    plan = ExperimentPlan(
        dataset="tiny", methods=("fedavg", "fedavg(meta)", "fomaml",
                                 "reptile"),
        rounds=3, eval_every=1, num_clients=12, clients_per_round=4,
        support_frac=0.5, support_size=8, query_size=8, inner_lr=0.1,
        outer_lr=0.05, local_lr=0.05, local_steps=2,
        data_fn=lambda n, s: _tiny_dataset(num_clients=n, seed=s),
        model_fn=lambda: _TinyModel)
    out = run_comparison(plan, out_dir=str(tmp_path), log=None)
    assert os.path.exists(out["path"])
    with open(out["path"]) as f:
        loaded = json.load(f)
    assert set(loaded["methods"]) == set(plan.methods)
    for m in plan.methods:
        hist = loaded["methods"][m]["history"]
        assert len(hist) == 3
        assert all("comm_MB" in r and "upload_MB" in r for r in hist)
        assert all("eval_acc" in r for r in hist)      # eval_every=1
    assert loaded["target_acc"] is not None
    assert set(loaded["comm_to_target"]) == set(plan.methods)
    # FedMeta and FedAvg methods were fed the SAME sampling stream:
    # identical per-round weighted training accuracy is too strong (the
    # client procedures differ), but comm accounting must agree on
    # rounds and the per-round download of a same-sized model
    fa = loaded["methods"]["fedavg"]["comm"]
    fm = loaded["methods"]["fomaml"]["comm"]
    assert fa["rounds"] == fm["rounds"] == 3
    assert fa["download_MB"] == pytest.approx(fm["download_MB"])


# ---- async round engine through the plane (DESIGN.md §12) ----------------

def _tiny_plan(**overrides):
    base = dict(
        dataset="tiny", methods=("fedavg", "fomaml"), rounds=4,
        eval_every=2, num_clients=12, clients_per_round=4,
        support_frac=0.5, support_size=8, query_size=8, inner_lr=0.1,
        outer_lr=0.05, local_lr=0.05, local_steps=2, pipeline="client_plane",
        data_fn=lambda n, s: _tiny_dataset(num_clients=n, seed=s),
        model_fn=lambda: _TinyModel)
    base.update(overrides)
    return ExperimentPlan(**base)


def test_make_trainer_rejects_unknown_pipeline():
    """A pipeline name other than "tree" or "client_plane" is refused
    for every method, not silently run on the tree path."""
    loss_fn, eval_fn = _loss_eval()
    for method in ("fomaml", "fedavg"):
        with pytest.raises(ValueError, match="unknown pipeline"):
            make_trainer(_tiny_plan(pipeline="client-plane"), method,
                         loss_fn, eval_fn, [])


def test_comparison_pipelined_bit_identical():
    """run_comparison on the pipelined path (prefetch + deferred
    metrics + fused-K) must reproduce the depth-0 comparison record —
    histories AND comm-to-target table — bit for bit."""
    sync = run_comparison(_tiny_plan(), save=False)
    piped = run_comparison(
        _tiny_plan(prefetch_depth=2, flush_every=4, fuse_rounds=2),
        save=False)
    for m in ("fedavg", "fomaml"):
        assert piped["methods"][m]["history"] == sync["methods"][m]["history"]
        assert piped["methods"][m]["comm"] == sync["methods"][m]["comm"]
    assert piped["comm_to_target"] == sync["comm_to_target"]
    assert piped["target_acc"] == sync["target_acc"]
    assert piped["plan"]["prefetch_depth"] == 2   # knob is serialized


def test_committed_artifacts_comm_to_target_stable():
    """The committed comparison artifacts pin the depth-0 behavior:
    recomputing every comm-to-target row from the stored histories must
    reproduce the stored table exactly — the engine refactor may not
    shift what the experiment plane would emit."""
    art_dir = os.path.join(os.path.dirname(__file__), "..",
                           "results", "experiments")
    # *_compare.json is the comparison-artifact naming convention;
    # other schemas (e.g. the §14 robustness sweep) live alongside
    paths = [os.path.join(art_dir, f) for f in sorted(os.listdir(art_dir))
             if f.endswith("_compare.json")]
    assert paths, "committed experiment artifacts are missing"
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        sustain = rec["plan"]["sustain_evals"]
        for m, row in rec["comm_to_target"].items():
            got = comm_to_target(rec["methods"][m]["history"],
                                 rec["target_acc"], sustain=sustain)
            if row is None:
                assert got is None, (path, m)
            else:
                pinned = {k: v for k, v in row.items()
                          if not k.startswith("comm_reduction")}
                assert got == pinned, (path, m)


def test_committed_compression_artifact_bytes_advantage():
    """The §17 acceptance pin, from the committed codec-axis artifact:
    every variant reaches the pinned target (accuracy inside the clean
    noise band by the sustain rule), upload accounting is codec-true
    (re-derivable from the stored comm fields), and at least one codec
    reaches the target at ≥3× fewer true transmitted upload bytes than
    the bf16 baseline path — compounding on bf16's own 2× over f32."""
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "experiments", "compression_femnist.json")
    assert os.path.exists(path), "committed compression artifact missing"
    with open(path) as f:
        rec = json.load(f)
    target, sustain = rec["target_acc"], rec["sustain_evals"]
    assert rec["baseline"] == "bf16"
    rows = {}
    for label, v in rec["variants"].items():
        row = v["comm_to_target"]
        assert row is not None, f"{label} missed the pinned target"
        # the stored row re-derives from the stored history (the same
        # pure-function pin as the *_compare.json artifacts)
        assert comm_to_target(v["history"], target,
                              sustain=sustain) == row, label
        rows[label] = row
        # upload accounting is codec-true: cumulative upload bytes are
        # rounds · m · per-client-bytes for the variant's wire format
        m = 4                                     # femnist registry m
        per_round = v["comm"]["upload_MB"] / v["comm"]["rounds"] / m
        if label == "f32":
            assert per_round * 1e6 == pytest.approx(
                v["comm"]["phi_MB"] * 1e6)
        elif label == "bf16":
            assert per_round * 1e6 == pytest.approx(
                v["comm"]["phi_MB"] * 1e6 / 2)
        else:
            assert v["comm"]["codec"] == label
            assert per_round < v["comm"]["phi_MB"] / 2   # beats bf16/rd
    ratios = rec["upload_to_target_ratio_vs_bf16"]
    assert max(ratios.get("int8+ef", 0.0),
               ratios.get("topk0.05+ef", 0.0)) >= 3.0, ratios
    for label, ratio in ratios.items():
        assert ratio == pytest.approx(
            rows["bf16"]["upload_MB"] / rows[label]["upload_MB"],
            rel=0.01), label
