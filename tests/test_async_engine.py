"""Async round engine invariants (DESIGN.md §12).

The engine ships on one invariant: with staleness off, any pipelined
configuration (prefetch_depth > 0, deferred flushes, fused-K blocks)
produces BIT-IDENTICAL history — metrics, comm, eval fields — to the
synchronous loop under the same seed. Plus: the staleness discount rule
against a hand-computed aggregate, and prefetcher shutdown (no leaked
threads) when either side of the pipeline raises.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import classification_loss, make_algorithm
from repro.core.fedmeta import init_packed_state, make_packed_meta_train_step
from repro.data.federated import ClientData, TaskStream, stack_task_batches
from repro.federated.async_engine import (PREFETCH_THREAD_NAME,
                                          AsyncRoundEngine, Prefetcher,
                                          StalenessConfig, plan_blocks)
from repro.federated.comm import CommTracker
from repro.federated.fedavg import FedAvgTrainer
from repro.federated.server import FederatedTrainer
from repro.optim import adam, sgd
from repro.utils.flat import plane_for

ALGOS = ("maml", "fomaml", "meta-sgd", "reptile")


def _tiny_clients(num=12, seed=0, feat=4, classes=2):
    rng = np.random.RandomState(seed)
    mu = rng.normal(0, 1, (classes, feat))
    clients = []
    for _ in range(num):
        n = rng.randint(10, 24)
        y = rng.randint(0, classes, (n,))
        x = mu[y] + rng.normal(0, 0.3, (n, feat))
        clients.append(ClientData(x.astype(np.float32), y.astype(np.int64)))
    return clients


class _TinyModel:
    @staticmethod
    def init(key):
        k, _ = jax.random.split(key)
        return {"w": jax.random.normal(k, (4, 2)) * 0.1,
                "b": jnp.zeros((2,))}

    @staticmethod
    def apply(params, x):
        return x @ params["w"] + params["b"]


LOSS_FN, EVAL_FN = classification_loss(_TinyModel.apply)
TRAIN = _tiny_clients()
EVAL = _tiny_clients(6, seed=1)


def _fedmeta_history(algo_name, *, packed, rounds=6, eval_every=3, **kw):
    algo = make_algorithm(algo_name, LOSS_FN, EVAL_FN, inner_lr=0.05)
    tr = FederatedTrainer(algo, adam(1e-3), TRAIN, 4, support_frac=0.5,
                          support_size=8, query_size=8, seed=0,
                          packed=packed, **kw)
    state = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
    tr.run(state, rounds, eval_every=eval_every, eval_clients=EVAL)
    return tr.history


def _no_prefetch_threads():
    return all(t.name != PREFETCH_THREAD_NAME for t in threading.enumerate())


# ---- bit-identity: pipelined == synchronous -----------------------------

@pytest.mark.parametrize("packed", [False, True],
                         ids=["tree", "packed"])
@pytest.mark.parametrize("algo_name", ALGOS)
def test_pipelined_history_bit_identical(algo_name, packed):
    """prefetch_depth>0 + deferred flushes == the synchronous loop,
    record for record (float equality, not allclose), for all four
    FedMeta algorithms on both parameter representations."""
    sync = _fedmeta_history(algo_name, packed=packed)
    piped = _fedmeta_history(algo_name, packed=packed, prefetch_depth=2,
                             flush_every=4)
    assert piped == sync
    assert _no_prefetch_threads()


def test_fused_k_history_bit_identical():
    """lax.scan-over-rounds blocks (fused-K) == per-round stepping,
    including an eval round that does not divide the block size and a
    flush only at exit."""
    sync = _fedmeta_history("fomaml", packed=True, rounds=7, eval_every=3)
    fused = _fedmeta_history("fomaml", packed=True, rounds=7, eval_every=3,
                             fuse_rounds=3, prefetch_depth=1, flush_every=0)
    assert fused == sync


def test_fedavg_pipelined_history_bit_identical():
    def run(**kw):
        tr = FedAvgTrainer(LOSS_FN, EVAL_FN, local_lr=1e-2, local_steps=2,
                           train_clients=TRAIN, clients_per_round=4,
                           support_frac=0.5, support_size=8, query_size=8,
                           seed=0, **kw)
        state = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
        tr.run(state, 6, eval_every=3, eval_clients=EVAL)
        return tr.history

    assert run(prefetch_depth=2, flush_every=3) == run()
    assert _no_prefetch_threads()


def test_plan_blocks():
    assert plan_blocks(5, 0, 1) == [1] * 5
    assert plan_blocks(10, 4, 3) == [3, 1, 3, 1, 2]   # eval rounds 4, 8
    assert plan_blocks(6, 2, 8) == [2, 2, 2]          # evals cap blocks
    assert plan_blocks(7, 3, 2) == [2, 1, 2, 1, 1]
    assert sum(plan_blocks(97, 10, 8)) == 97


# ---- staleness-aware aggregation ----------------------------------------

def test_staleness_discount_hand_check():
    """The γ^s rule, against a hand-built aggregate: round 1's straggler
    row must arrive in round 2 weighted by its ORIGINAL round-1 weight
    times discount**delay, renormalized over the aggregated rows."""
    cfg = StalenessConfig(delay=1, fraction=0.34, discount=0.5)
    assert cfg.num_stragglers(3) == 1
    algo = make_algorithm("fomaml", LOSS_FN, EVAL_FN, inner_lr=0.05)
    phi = algo.init_state(jax.random.PRNGKey(0), _TinyModel.init)
    plane = plane_for(phi)
    opt = sgd(0.1)
    step = make_packed_meta_train_step(algo, opt, plane, staleness=cfg)
    state = init_packed_state(opt, plane, phi, staleness=cfg,
                              clients_per_round=3)

    rng = np.random.RandomState(3)
    stream = TaskStream(TRAIN, 3, 0.5, 8, 8, rng)
    tb1, tb2 = stream.next(), stream.next()

    def args(tb):
        return ((jnp.asarray(tb.support_x), jnp.asarray(tb.support_y)),
                (jnp.asarray(tb.query_x), jnp.asarray(tb.query_y)),
                jnp.asarray(tb.weight))

    def rows(tb, phi_tree):
        return np.stack([
            np.asarray(plane.pack(algo.client_grad(
                phi_tree, (tb.support_x[i], tb.support_y[i]),
                (tb.query_x[i], tb.query_y[i]))[0]))
            for i in range(3)])

    sel1 = (jnp.asarray([1], jnp.int32), jnp.asarray([0, 2], jnp.int32))
    sel2 = (jnp.asarray([0], jnp.int32), jnp.asarray([1, 2], jnp.int32))

    # round 1: straggler row 1 is withheld; warmup slot has weight 0
    g1 = rows(tb1, phi)
    w1 = tb1.weight / tb1.weight.sum()
    exp1 = (w1[0] * g1[0] + w1[2] * g1[2]) / (w1[0] + w1[2])
    state1, _ = step(state, *args(tb1), sel1)
    flat0 = np.asarray(plane.pack(phi))
    np.testing.assert_allclose(np.asarray(state1["phi"]),
                               flat0 - 0.1 * exp1, rtol=1e-5, atol=1e-7)

    # round 2: row 1 of round 1 arrives at weight w1[1] * γ^1, fresh
    # rows are computed against the ADVANCED φ; renormalize over rows
    phi1 = plane.unpack(state1["phi"])
    g2 = rows(tb2, phi1)
    w2 = tb2.weight / tb2.weight.sum()
    gamma = cfg.discount ** cfg.delay
    num = w2[1] * g2[1] + w2[2] * g2[2] + gamma * w1[1] * g1[1]
    exp2 = num / (w2[1] + w2[2] + gamma * w1[1])
    flat1 = np.asarray(state1["phi"])    # the step donates state1
    state2, _ = step(state1, *args(tb2), sel2)
    np.testing.assert_allclose(
        np.asarray(state2["phi"]), flat1 - 0.1 * exp2,
        rtol=1e-5, atol=1e-7)
    # the new straggler (row 0 of round 2) sits in the ring buffer
    np.testing.assert_allclose(np.asarray(state2["stale"]["G"][0, 0]), g2[0],
                               rtol=1e-5, atol=1e-7)
    assert np.isclose(float(state2["stale"]["w"][0, 0]), w2[0])


def test_staleness_off_is_bitwise_noop():
    """fraction=0 staleness must not change the trajectory: every round
    aggregates m fresh rows at their full weights."""
    base = _fedmeta_history("fomaml", packed=True)
    zero = _fedmeta_history(
        "fomaml", packed=True,
        staleness=StalenessConfig(delay=1, fraction=0.0, discount=0.5))
    assert [{k: v for k, v in r.items()} for r in zero] == base


def test_staleness_jitter_off_bitwise_identical(monkeypatch):
    """jitter=False must be bitwise-identical to the pre-jitter
    fixed-delay behavior: same rng draw pattern (no extra randint),
    same step path. Pinned by monkeypatching `pick` back to the legacy
    implementation and comparing record-for-record."""
    cfg = StalenessConfig(delay=2, fraction=0.34, discount=0.5)
    assert cfg.jitter is False
    off = _fedmeta_history("fomaml", packed=True, staleness=cfg)

    def legacy_pick(self, m, rng):
        k = self.num_stragglers(m)
        perm = rng.permutation(m)
        return (np.sort(perm[:k]).astype(np.int32),
                np.sort(perm[k:]).astype(np.int32))

    monkeypatch.setattr(StalenessConfig, "pick", legacy_pick)
    legacy = _fedmeta_history("fomaml", packed=True, staleness=cfg)
    assert off == legacy


def test_staleness_jitter_hand_check():
    """Jittered staleness against an independent reference simulator:
    per-straggler delays d ∈ [0, delay], arrival at round r+d with
    weight w·γ^d (d=0 joins its own round like a fresh row), weights
    renormalized over the rows aggregated that round — including a
    round where TWO earlier stragglers (d=2 and d=1) arrive together."""
    cfg = StalenessConfig(delay=2, fraction=0.34, discount=0.5, jitter=True)
    algo = make_algorithm("fomaml", LOSS_FN, EVAL_FN, inner_lr=0.05)
    phi0 = algo.init_state(jax.random.PRNGKey(0), _TinyModel.init)
    plane = plane_for(phi0)
    opt = sgd(0.1)
    step = make_packed_meta_train_step(algo, opt, plane, staleness=cfg)
    state = init_packed_state(opt, plane, phi0, staleness=cfg,
                              clients_per_round=3)
    assert set(state["stale"]) == {"G", "w", "c", "d"}

    rng = np.random.RandomState(3)
    stream = TaskStream(TRAIN, 3, 0.5, 8, 8, rng)
    tbs = stream.take(5)
    # (straggler, fresh, delays) per round — exercises d=1, d=0
    # (immediate join), d=2, and a double arrival in round 5
    sels = [([1], [0, 2], [1]), ([0], [1, 2], [0]), ([2], [0, 1], [2]),
            ([0], [1, 2], [1]), ([1], [0, 2], [0])]

    def rows(tb, phi_tree):
        return [np.asarray(plane.pack(algo.client_grad(
            phi_tree, (tb.support_x[i], tb.support_y[i]),
            (tb.query_x[i], tb.query_y[i]))[0])) for i in range(3)]

    # ---- independent reference: pending-arrival list, no ring buffer
    flat = np.asarray(plane.pack(phi0))
    expected = []
    pending = []   # (arrive_round, weight*gamma^d, gradient row)
    for r, (tb, (strag, fresh, delays)) in enumerate(zip(tbs, sels), start=1):
        g = rows(tb, plane.unpack(jnp.asarray(flat)))
        w = tb.weight / tb.weight.sum()
        agg = [(w[i], g[i]) for i in fresh]
        for j, d in zip(strag, delays):
            if d == 0:
                agg.append((w[j], g[j]))
            else:
                pending.append((r + d, cfg.discount ** d * w[j], g[j]))
        agg += [(pw, pg) for (ar, pw, pg) in pending if ar == r]
        pending = [p for p in pending if p[0] != r]
        tot = sum(pw for pw, _ in agg)
        flat = flat - 0.1 * sum(pw * pg for pw, pg in agg) / tot
        expected.append(flat.copy())

    # ---- the jitted step, same schedule
    for tb, (strag, fresh, delays) in zip(tbs, sels):
        sel = (jnp.asarray(strag, jnp.int32), jnp.asarray(fresh, jnp.int32),
               jnp.asarray(delays, jnp.int32))
        state, _ = step(state,
                        (jnp.asarray(tb.support_x), jnp.asarray(tb.support_y)),
                        (jnp.asarray(tb.query_x), jnp.asarray(tb.query_y)),
                        jnp.asarray(tb.weight), sel)
    np.testing.assert_allclose(np.asarray(state["phi"]), expected[-1],
                               rtol=1e-5, atol=1e-7)


def test_staleness_jitter_runs_through_trainer():
    """The trainer wires the 3-tuple pick through staging/prefetch; a
    jittered run completes and (generically) diverges from fixed-delay."""
    fixed = _fedmeta_history(
        "fomaml", packed=True,
        staleness=StalenessConfig(delay=2, fraction=0.34, discount=0.5))
    jit = _fedmeta_history(
        "fomaml", packed=True, prefetch_depth=2,
        staleness=StalenessConfig(delay=2, fraction=0.34, discount=0.5,
                                  jitter=True))
    assert len(jit) == len(fixed)
    assert jit != fixed
    assert _no_prefetch_threads()


def test_staleness_validation():
    algo = make_algorithm("fomaml", LOSS_FN, EVAL_FN, inner_lr=0.05)
    with pytest.raises(ValueError):
        FederatedTrainer(algo, adam(1e-3), TRAIN, 4, support_frac=0.5,
                         support_size=8, query_size=8,
                         staleness=StalenessConfig())       # needs packed
    with pytest.raises(ValueError):
        FederatedTrainer(algo, adam(1e-3), TRAIN, 4, support_frac=0.5,
                         support_size=8, query_size=8, packed=True,
                         client_axis="chunked", client_chunk=2,
                         staleness=StalenessConfig())       # needs vmap
    with pytest.raises(ValueError):
        StalenessConfig(delay=0)
    with pytest.raises(ValueError):
        StalenessConfig(fraction=1.0)


# ---- prefetcher lifecycle ----------------------------------------------

def test_step_exception_shuts_down_prefetcher():
    """A step that raises mid-run must not leak the prefetch thread,
    and the rounds completed before the failure must still be flushed
    to history."""
    algo = make_algorithm("fomaml", LOSS_FN, EVAL_FN, inner_lr=0.05)
    tr = FederatedTrainer(algo, adam(1e-3), TRAIN, 4, support_frac=0.5,
                          support_size=8, query_size=8, seed=0, packed=True,
                          prefetch_depth=3, flush_every=0)
    state = tr.init(jax.random.PRNGKey(0), _TinyModel.init)
    real_step, calls = tr._step, []

    def boom(st, *a):
        calls.append(1)
        if len(calls) == 3:
            raise RuntimeError("client exploded")
        return real_step(st, *a)

    tr._step = boom
    with pytest.raises(RuntimeError, match="client exploded"):
        tr.run(state, 10)
    assert _no_prefetch_threads()
    assert [r["round"] for r in tr.history] == [1, 2]  # flushed on exit


def test_producer_exception_propagates_and_joins():
    """An exception raised while sampling/staging on the background
    thread re-raises at the consumer and the thread exits."""
    def produce(k):
        if produce.calls == 1:
            raise ValueError("bad sample")
        produce.calls += 1
        return k

    produce.calls = 0
    pf = Prefetcher(produce, [1, 1, 1], depth=2)
    assert pf.get() == 1
    with pytest.raises(ValueError, match="bad sample"):
        pf.get()
    pf.close()
    assert not pf.alive


def test_engine_defers_flush_to_cadence():
    """flush_every batches history materialization without changing the
    records; flush_every=0 drains only at exit."""
    comm = CommTracker(phi_bytes=1000, clients_per_round=2)
    history, seen = [], []

    def stage(k):
        return jnp.float32(k)

    def step(state, staged):
        return state + 1, {"loss": jnp.float32(state)}

    engine = AsyncRoundEngine(stage=stage, step=step, comm=comm,
                              history=history, flush_every=3)
    engine.run(0, 7, log=lambda rec: seen.append(rec["round"]))
    assert [r["round"] for r in history] == list(range(1, 8))
    assert [r["loss"] for r in history] == [float(i) for i in range(7)]
    assert history[-1]["comm_MB"] == comm.summary()["comm_MB"]
    assert seen == list(range(1, 8))


def test_stack_task_batches_round_axis():
    rng = np.random.RandomState(0)
    stream = TaskStream(TRAIN, 4, 0.5, 8, 8, rng)
    tbs = stream.take(3)
    stacked = stack_task_batches(tbs)
    assert stacked.support_x.shape == (3, 4, 8, 4)
    np.testing.assert_array_equal(stacked.weight[1], tbs[1].weight)
