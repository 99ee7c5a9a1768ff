"""Federated training drivers: server round loop, client sampling,
communication accounting, and the paper's evaluation schemes.

Evaluation (paper §4.1 + A.2): accuracy w.r.t. all data points on held-out
*test clients*; each test client adapts on its support set (FedMeta /
FedAvg(Meta)) or not (FedAvg) and is scored on its query set.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.fedmeta import (_maybe_jit, _resolve_mesh,
                                check_plane_composition, init_packed_state,
                                make_meta_train_step,
                                make_packed_meta_train_step)
from repro.data.federated import (TaskStream, assemble_task_batch,
                                  sample_task_batch, stack_task_batches)
from repro.federated.async_engine import (AsyncRoundEngine, StalenessConfig,
                                          WorkerPool)
from repro.federated.comm import CommTracker, measure_client_flops
from repro.federated.faults import FaultConfig
from repro.federated.privacy import DPConfig
from repro.kernels.meta_update.compress import CompressionConfig
from repro.federated.population import (CircuitBreaker, UnreliabilityConfig,
                                        plan_round)
from repro.optim import Optimizer
from repro.utils.flat import plane_for
from repro.utils.trace import span


def _rng_state_payload(state):
    """np.random.RandomState.get_state() tuple -> checkpointable dict
    (the 624-word key vector as an array, scalars as python types)."""
    alg, keys, pos, has_gauss, cached = state
    return {"alg": alg, "keys": np.asarray(keys, np.uint32),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached": float(cached)}


def _rng_state_from_payload(p):
    return (str(p["alg"]), np.asarray(p["keys"], np.uint32),
            int(p["pos"]), int(p["has_gauss"]), float(p["cached"]))


def _batch_eval(eval_one, clients, m, support_frac, support_size, query_size,
                rng):
    tb = sample_task_batch(clients, m, support_frac, support_size, query_size,
                           rng)
    accs, losses = eval_one((tb.support_x, tb.support_y),
                            (tb.query_x, tb.query_y))
    counts = (np.ones((m,), np.float64) if tb.query_count is None
              else np.asarray(tb.query_count, np.float64))
    return np.asarray(accs), np.asarray(losses), counts


def _count_weighted(accs, losses, counts):
    """§4.1 evaluation: accuracy w.r.t. *all data points*, i.e. each
    client's (fixed-shape resampled) query accuracy weighted by the
    number of query examples that client actually holds — not an
    unweighted mean over clients. Same reduction for the loss."""
    w = counts / counts.sum()
    return float(np.sum(w * accs)), float(np.sum(w * losses))


def make_meta_evaluator(algo, adapt_steps=None):
    """Jitted once; φ passed as an argument (avoids per-eval recompiles)."""

    @jax.jit
    def eval_batch(phi, support, query):
        def one(s, q):
            theta_u = algo.adapt(phi, s, steps=adapt_steps)
            loss, met = algo.eval_fn(theta_u, q)
            return met["accuracy"], loss
        return jax.vmap(one)(support, query)

    return eval_batch


def make_global_evaluator(eval_fn, finetune: Optional[Callable] = None):
    @jax.jit
    def eval_batch(theta, support, query):
        def one(s, q):
            th = theta if finetune is None else finetune(theta, s)
            loss, met = eval_fn(th, q)
            return met["accuracy"], loss
        return jax.vmap(one)(support, query)

    return eval_batch


def evaluate_meta(algo, phi, clients, *, support_frac, support_size,
                  query_size, seed=0, adapt_steps=None, evaluator=None):
    """Per-client adapted accuracy over all test clients; returns
    (acc, per_client_accs, mean_loss) with acc and mean_loss weighted by
    each client's true query count (§4.1). Pass a `make_meta_evaluator`
    result to amortize compilation across calls."""
    rng = np.random.RandomState(seed)
    ev = evaluator or make_meta_evaluator(algo, adapt_steps)
    accs, losses, counts = _batch_eval(
        lambda s, q: ev(phi, s, q), clients, len(clients), support_frac,
        support_size, query_size, rng)
    acc, loss = _count_weighted(accs, losses, counts)
    return acc, accs, loss


def evaluate_global(eval_fn, theta, clients, *, support_frac, support_size,
                    query_size, seed=0, finetune: Optional[Callable] = None,
                    evaluator=None):
    """FedAvg (finetune=None) / FedAvg(Meta) (finetune=trainer.finetune).
    Returns (acc, per_client_accs, mean_loss), query-count-weighted like
    `evaluate_meta`."""
    rng = np.random.RandomState(seed)
    ev = evaluator or make_global_evaluator(eval_fn, finetune)
    accs, losses, counts = _batch_eval(
        lambda s, q: ev(theta, s, q), clients, len(clients), support_frac,
        support_size, query_size, rng)
    acc, loss = _count_weighted(accs, losses, counts)
    return acc, accs, loss


def _put(dp, tree, round_):
    """Stage every host array of ``tree`` with ``dp`` (``None``s kept),
    under one span whose stat ``bytes`` is their total size."""
    nbytes = sum(x.nbytes for x in jax.tree.leaves(tree))
    with span("fedmeta.round.put", round=round_, bytes=nbytes):
        return jax.tree.map(dp, tree)


@dataclasses.dataclass
class FederatedTrainer:
    """FedMeta meta-training loop (Algorithm 1 AlgorithmUpdate)."""
    algo: object
    optimizer: Optimizer
    train_clients: list
    clients_per_round: int
    support_frac: float
    support_size: int
    query_size: int
    weighted: bool = True          # paper A.2: weight by local data count
    client_axis: str = "vmap"
    seed: int = 0
    client_chunk: Optional[int] = None   # for client_axis="chunked"
    packed: bool = False                 # the flat pipeline (client plane)
    impl: Optional[str] = None           # fused-kernel impl for packed
    block_dtype: Optional[object] = None  # client-grad block dtype (packed)
    mesh: Optional[object] = None  # for client_axis="sharded" (None =
    mesh_axis: Optional[str] = None  # ambient mesh, first axis)
    # ---- async round engine (DESIGN.md §12) -------------------------
    prefetch_depth: int = 0     # staged round blocks ahead; 0 = sync loop
    flush_every: int = 1        # drain deferred metrics every k rounds
                                # (0 = only at eval rounds / run() exit)
    fuse_rounds: int = 1        # lax.scan-over-rounds block size (packed)
    staleness: Optional[StalenessConfig] = None  # packed + vmap axis only
    # ---- failure plane (DESIGN.md §14) ------------------------------
    aggregator: str = "mean"    # mean | masked_mean | screen | trimmed
    screen_factor: float = 3.0  # screen: clip rows > factor × median ‖g‖
    trim: int = 1               # trimmed: per-coordinate trim count
    faults: Optional[FaultConfig] = None  # packed + vmap axis only
    guard: Optional[bool] = None  # non-finite skip-round guard; None =
                                  # auto (on iff faults or robust agg)
    # ---- bytes-on-the-wire plane (DESIGN.md §17) --------------------
    compression: Optional[CompressionConfig] = None  # packed + vmap only
    dp: Optional[DPConfig] = None  # central-DP clip+noise (packed + vmap)
    prefetch_retries: int = 0   # transient staging failures retried
    checkpoint_every: int = 0   # rounds between checkpoints (0 = off)
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: int = 3    # keep-last-k retention
    # ---- population plane (DESIGN.md §15) ---------------------------
    unreliability: Optional[UnreliabilityConfig] = None  # arrival model
    over_select: float = 0.0    # sample m·(1+over_select) candidates
    round_deadline: Optional[float] = None  # latency cutoff (unrel units)
    pool_workers: int = 0       # shard-materializing workers (0 = inline)
    pool_retries: int = 2       # per-shard retry-with-backoff budget
    task_timeout: Optional[float] = None    # per-shard pool timeout (s)
    breaker_threshold: int = 3  # consecutive failures before quarantine
    breaker_cooldown: int = 10  # quarantine length in rounds

    def __post_init__(self):
        if self.over_select < 0:
            raise ValueError("over_select must be >= 0")
        check_plane_composition(
            self.client_axis, aggregator=self.aggregator,
            staleness=self.staleness, faults=self.faults,
            compression=self.compression, dp=self.dp)
        pop = self._population_active
        if not self.packed:
            flat_only = {
                "the population plane (unreliability / over_select / "
                "round_deadline)": pop,
                "fuse_rounds>1": self.fuse_rounds > 1,
                "staleness": self.staleness is not None,
                "faults": self.faults is not None,
                f"aggregator={self.aggregator!r}": self.aggregator != "mean",
                "compression": self.compression is not None,
                "DP": self.dp is not None,
                "the non-finite guard": bool(self.guard),
                "block_dtype": self.block_dtype is not None}
            on = [name for name, used in flat_only.items() if used]
            if on:
                raise ValueError(f"these modes run on the flat pipeline "
                                 f"only (packed=True): {', '.join(on)}")
        if pop:
            if self.client_axis != "vmap":
                raise ValueError("the population plane (unreliability / "
                                 "over_select / round_deadline) needs "
                                 "the full (m, N) client block — "
                                 "client_axis='vmap'")
            if self.fuse_rounds > 1:
                raise ValueError("the population plane and fuse_rounds>1 "
                                 "are mutually exclusive (arrival plans "
                                 "are per-round)")
            if self.staleness is not None:
                raise ValueError("staleness simulation and the population "
                                 "plane are mutually exclusive — the "
                                 "deadline model already decides who "
                                 "arrives late")
            if self.compression is not None or self.dp is not None:
                raise ValueError("compression / DP and the population "
                                 "plane are mutually exclusive")
            if self.aggregator == "mean":
                # partial rounds need the renormalizing aggregator:
                # zero-weight pad rows must be exact no-ops
                self.aggregator = "masked_mean"
        if self.fuse_rounds > 1:
            for name, on in (("staleness", self.staleness is not None),
                             ("faults", self.faults is not None),
                             ("compression / DP", self.compression
                              is not None or self.dp is not None)):
                if on:
                    raise ValueError(f"{name} and fuse_rounds>1 are "
                                     f"mutually exclusive (each round "
                                     f"takes its own inputs)")
        if self.aggregator == "trimmed" and \
                2 * self.trim >= self.clients_per_round:
            raise ValueError(f"trimmed mean needs 2·trim < clients_per_"
                             f"round ({self.trim} vs "
                             f"{self.clients_per_round})")
        if self.guard is None:
            # auto: any failure-plane knob needs skip-round semantics
            self.guard = (self.faults is not None or
                          self.aggregator != "mean")
        # the packed step needs φ's FlatPlane, built in init(); the tree
        # step has no such dependency and is built eagerly
        self._step = None if self.packed else make_meta_train_step(
            self.algo, self.optimizer, client_axis=self.client_axis,
            client_chunk=self.client_chunk, mesh=self.mesh,
            mesh_axis=self.mesh_axis)
        self._fused = None
        self._plane = None
        self._rng = np.random.RandomState(self.seed)
        self._stale_rng = (np.random.RandomState(self.staleness.seed)
                           if self.staleness is not None else None)
        self._fault_rng = (np.random.RandomState(self.faults.seed)
                           if self.faults is not None else None)
        self._rng_snaps: dict = {}   # round -> rng states (prefetch-safe)
        self._breaker = (CircuitBreaker(self.breaker_threshold,
                                        self.breaker_cooldown)
                         if self._population_active else None)
        self._pool: Optional[WorkerPool] = None
        self._evaluator = make_meta_evaluator(self.algo)
        self.comm: Optional[CommTracker] = None
        self.history: list = []

    @property
    def _population_active(self) -> bool:
        """Deadline/over-selection staging replaces the plain task
        stream. A bare pool (pool_workers>0, everything else off) is
        NOT population mode — it only pre-warms the registry cache, so
        staging stays bit-identical to the eager path."""
        return (self.unreliability is not None or self.over_select > 0
                or self.round_deadline is not None)

    def init(self, key, model_init):
        phi = self.algo.init_state(key, model_init)
        if self.packed:
            self._plane = plane_for(phi)
            kw = dict(client_axis=self.client_axis,
                      client_chunk=self.client_chunk, impl=self.impl,
                      block_dtype=self.block_dtype,
                      staleness=self.staleness,
                      aggregator=self.aggregator,
                      screen_factor=self.screen_factor, trim=self.trim,
                      faults=self.faults, guard=bool(self.guard),
                      compression=self.compression, dp=self.dp,
                      mesh=self.mesh, mesh_axis=self.mesh_axis)
            self._step = make_packed_meta_train_step(
                self.algo, self.optimizer, self._plane, **kw)
            if self.fuse_rounds > 1:
                # scan-over-rounds on the SAME (unjitted) step body the
                # per-round path compiles — fused-K blocks must be
                # bit-identical to K per-round steps
                body = make_packed_meta_train_step(
                    self.algo, self.optimizer, self._plane, jit=False,
                    donate=False, **kw)

                def fused(state, staged):
                    def one(st, inp):
                        sup, qry, w = inp
                        return body(st, sup, qry, w)
                    return jax.lax.scan(one, state, staged)

                self._fused = _maybe_jit(fused, True, True)
            state = init_packed_state(
                self.optimizer, self._plane, phi, staleness=self.staleness,
                clients_per_round=self.clients_per_round,
                block_dtype=self.block_dtype,
                compression=self.compression,
                num_clients=len(self.train_clients))
        else:
            state = {"phi": phi, "opt": self.optimizer.init(phi)}
        self.comm = CommTracker.for_state(
            phi, self.clients_per_round,
            block_dtype=self.block_dtype if self.packed else None)
        if self.packed and self.compression is not None:
            # codec-true upload bytes (§17): payload + side information
            # over the REAL parameter count; top-k values ride at the
            # block dtype's width. Download stays dense φ.
            from repro.utils.pytree import tree_size
            val_itemsize = jnp.dtype(
                self.block_dtype or jnp.float32).itemsize
            self.comm.grad_bytes = self.compression.upload_bytes(
                tree_size(phi), val_itemsize)
            self.comm.codec = self.compression.label()
        return state

    def phi_tree(self, state):
        """φ as a pytree regardless of parameter representation."""
        if self.packed:
            return self._plane.unpack(state["phi"])
        return state["phi"]

    def evaluator(self):
        """The trainer's jitted meta-evaluator — pass to `evaluate_meta`
        to amortize compilation across eval calls."""
        return self._evaluator

    def measure_flops(self, state):
        """One-off XLA cost analysis of the client procedure."""
        tb = sample_task_batch(self.train_clients, 1, self.support_frac,
                               self.support_size, self.query_size, self._rng)
        sup = jax.tree.map(lambda x: jnp.asarray(x[0]),
                           (tb.support_x, tb.support_y))
        qry = jax.tree.map(lambda x: jnp.asarray(x[0]),
                           (tb.query_x, tb.query_y))
        fl = measure_client_flops(
            lambda s, q: self.algo.client_grad(self.phi_tree(state), s, q)[0],
            sup, qry)
        if self.comm:
            self.comm.flops_per_client = fl
        return fl

    def placement(self) -> Callable:
        """The ``device_put`` that stages round inputs. On the sharded
        client axis every chip receives its own clients' rows straight
        from the host — split along the leading client axis when the
        mesh axis divides it — instead of all rows landing on the
        default device and crossing to the others inside the step.
        Elsewhere: the default device."""
        if self.client_axis != "sharded" or self.fuse_rounds > 1:
            return jax.device_put
        mesh, ax = _resolve_mesh(self.mesh, self.mesh_axis)
        n = mesh.shape[ax]
        sharding = NamedSharding(mesh, P(ax))

        def put(x):
            if np.ndim(x) and np.shape(x)[0] % n == 0:
                return jax.device_put(x, sharding)
            return jax.device_put(x)
        return put

    def _place_state(self, state):
        """Put a train state where the step returns it. On the sharded
        client axis that is replicated over the client mesh: a state
        left on one device (fresh from ``init`` or ``resume``) would
        have the step compile once for it and again for the replicated
        state it returns. Elsewhere the state is left as it is."""
        if self.client_axis != "sharded":
            return state
        mesh, _ = _resolve_mesh(self.mesh, self.mesh_axis)
        return jax.device_put(state, NamedSharding(mesh, P()))

    def _stage_block(self, stream, dp, k, round_):
        """Host half of one round block: sample + device_put staging.
        Runs on the prefetch thread (in block order) when pipelined.

        The step's optional inputs are positional —
        ``(stale_sel, fault, ef_idx, dp_key)`` — staged as a tail with
        trailing ``None``s trimmed, so every off-knob configuration
        stages byte-for-byte the argument tuple it staged before the
        knob existed (the PR 4–7 shipping invariant)."""
        with span("fedmeta.round.sample", round=round_):
            if k > 1:   # fused-K: one stacked (k, m, ...) staged buffer
                tb = stack_task_batches(stream.take(k))
                tail = [None] * 4
            else:
                tb = stream.next()
                tail = self._round_tail(tb, round_)
            args = ((tb.support_x, tb.support_y), (tb.query_x, tb.query_y),
                    tb.weight if self.weighted else None)
        # the dp key is made on the device already; the rest is staged
        args, tail[:3] = _put(dp, (args, tail[:3]), round_)
        while tail and tail[-1] is None:
            tail.pop()
        return args + tuple(tail)

    def _round_tail(self, tb, round_) -> list:
        """The host draws of one round's optional inputs,
        ``[stale_sel, fault, ef_idx, dp_key]`` (``None`` where off)."""
        sel = fault = ef_idx = dp_key = None
        if self.staleness is not None:
            # (straggler_idx, fresh_idx[, delays]) — delays only
            # with jitter on, so the off-path stays bit-identical
            sel = self.staleness.pick(self.clients_per_round,
                                      self._stale_rng)
        if self.faults is not None:
            fault = tuple(self.faults.pick(self.clients_per_round,
                                           self._fault_rng))
        if self.compression is not None and \
                self.compression.error_feedback:
            # this round's picks = the residual-plane rows the step
            # gathers/scatters (recorded by the sampler; no extra draw)
            ef_idx = np.asarray(tb.client_idx, np.int32)
        if self.dp is not None and self.dp.noise_multiplier > 0:
            # pure function of the round index: prefetch/resume-safe
            # with nothing checkpointed
            dp_key = self.dp.round_key(round_)
        return [sel, fault, ef_idx, dp_key]

    # ---- population plane (DESIGN.md §15) ---------------------------
    def _peek_picks(self):
        """The upcoming task batch's client picks without consuming the
        stream — the rng state is saved and restored, so the subsequent
        real draw replays identically (pool cache pre-warming)."""
        st = self._rng.get_state()
        n = len(self.train_clients)
        picks = self._rng.choice(n, size=self.clients_per_round,
                                 replace=n < self.clients_per_round)
        self._rng.set_state(st)
        return picks

    def _stage_population(self, dp, round_):
        """Host half of one population-plane round: sample
        ``m·(1+over_select)`` non-quarantined candidates, compute the
        deterministic arrival plan, materialize the arrived shards
        (through the worker pool when configured), and build the
        zero-weight-padded batch the `masked_mean` step renormalizes.
        Runs on the prefetch thread (in round order) when pipelined."""
        with span("fedmeta.round.sample", round=round_):
            args, fault = self._sample_population(round_)
        args, fault = _put(dp, (args, fault), round_)
        if self.faults is not None:
            args += (None, fault)   # stale_sel placeholder (positional)
        return args

    def _sample_population(self, round_):
        """-> (host batch arrays, fault draw or None) of one round."""
        clients = self.train_clients
        m = self.clients_per_round
        rng = self._rng
        n_cand = m + int(round(self.over_select * m))
        quar = self._breaker.blocked(round_)
        n_total = len(clients)
        if quar and len(quar) < n_total:
            avail = np.setdiff1d(np.arange(n_total, dtype=np.int64),
                                 np.fromiter(quar, np.int64, len(quar)))
            cand = avail[rng.choice(len(avail), size=n_cand,
                                    replace=len(avail) < n_cand)]
        else:
            cand = rng.choice(n_total, size=n_cand,
                              replace=n_total < n_cand).astype(np.int64)
        plan = plan_round(cand, round_, self.unreliability,
                          self.round_deadline, m)
        for c in plan.failed:
            self._breaker.record_failure(int(c), round_)
        for c in plan.arrived:
            self._breaker.record_success(int(c))
        idxs = [int(c) for c in plan.arrived]
        label = f"round {round_}"
        if self._pool is not None:
            shards = self._pool.map(idxs, label=label)
            probe = (None if idxs else
                     self._pool.map([int(cand[0])], label=label)[0])
        else:
            shards = [clients[i] for i in idxs]
            probe = None if idxs else clients[int(cand[0])]
        tb = assemble_task_batch(shards, m, self.support_frac,
                                 self.support_size, self.query_size, rng,
                                 weighted=self.weighted, probe=probe)
        # download: φ went to every candidate; upload: only arrivals
        self.comm.record_round(len(cand), len(idxs), len(quar))
        # weights always staged: the zero rows ARE the arrival mask
        args = ((tb.support_x, tb.support_y), (tb.query_x, tb.query_y),
                tb.weight)
        fault = None
        if self.faults is not None:
            fault = tuple(self.faults.pick(m, self._fault_rng))
        return args, fault

    # ---- crash-safe checkpointing (DESIGN.md §14) -------------------
    def _capture_rngs(self):
        """Snapshot every host-side seeded/stateful stream the run
        consumes (the breaker and participation log ride along — they
        mutate at staging time, so retry/resume must roll them back
        with the rngs)."""
        snap = {"task": self._rng.get_state()}
        if self._stale_rng is not None:
            snap["stale"] = self._stale_rng.get_state()
        if self._fault_rng is not None:
            snap["fault"] = self._fault_rng.get_state()
        if self._breaker is not None:
            snap["breaker"] = self._breaker.state_dict()
            snap["participation"] = (list(self.comm.participation)
                                     if self.comm is not None else [])
        return snap

    def _restore_rngs(self, snap):
        self._rng.set_state(snap["task"])
        if self._stale_rng is not None:
            self._stale_rng.set_state(snap["stale"])
        if self._fault_rng is not None:
            self._fault_rng.set_state(snap["fault"])
        if self._breaker is not None and "breaker" in snap:
            self._breaker.load_state(snap["breaker"])
            if self.comm is not None:
                self.comm.participation[:] = snap.get("participation", [])

    def save_checkpoint(self, state, round_: int, ckpt_dir=None) -> str:
        """Write one atomic checkpoint capturing everything a resumed
        run needs for bit-identical history: train state (φ, optimizer,
        staleness ring), the RNG states *as of round ``round_``* (under
        prefetching the live streams have already advanced past the
        checkpointed round — the engine hook uses the snapshot staged
        at that round's block boundary), CommTracker counters, and the
        flushed history."""
        from repro.checkpoint.io import save_server_state
        snap = self._rng_snaps.pop(round_, None) or self._capture_rngs()
        self._rng_snaps = {r: s for r, s in self._rng_snaps.items()
                           if r > round_}
        payload = {
            "round": int(round_),
            "state": state,
            "rng": {k: _rng_state_payload(snap[k])
                    for k in ("task", "stale", "fault") if k in snap},
            "comm_rounds": int(self.comm.rounds),
            "flops_per_client": float(self.comm.flops_per_client or 0.0),
            "history": list(self.history),
        }
        if "breaker" in snap:      # population plane host state
            payload["breaker"] = snap["breaker"]
            payload["participation"] = [list(p) for p in
                                        snap.get("participation", [])]
        return save_server_state(ckpt_dir or self.checkpoint_dir,
                                 round_, payload,
                                 keep_last=self.checkpoint_keep)

    def resume(self, ckpt_dir=None, step: int | None = None):
        """Restore a killed run from its latest (or ``step``-numbered)
        checkpoint. Call after ``init()``; returns ``(state,
        start_round)`` for ``run(state, rounds,
        start_round=start_round)`` — the resumed tail reproduces the
        uninterrupted run's history record-for-record."""
        from repro.checkpoint.io import load_server_state
        payload = load_server_state(ckpt_dir or self.checkpoint_dir, step)
        for name, rng in (("task", self._rng), ("stale", self._stale_rng),
                          ("fault", self._fault_rng)):
            if name in payload["rng"] and rng is not None:
                rng.set_state(_rng_state_from_payload(
                    payload["rng"][name]))
        self.comm.rounds = int(payload["comm_rounds"])
        if payload["flops_per_client"]:
            self.comm.flops_per_client = payload["flops_per_client"]
        if self._breaker is not None and payload.get("breaker") is not None:
            self._breaker.load_state(payload["breaker"])
        self.comm.participation[:] = [
            tuple(int(x) for x in p)
            for p in payload.get("participation", [])]
        self.history[:] = payload["history"]
        state = payload["state"]
        return state, int(payload["round"])

    def run(self, state, rounds: int, eval_every: int = 0,
            eval_clients=None, log: Callable = None,
            start_round: int = 0):
        """Drive ``rounds`` rounds through the async round engine
        (DESIGN.md §12). The default knobs (prefetch_depth=0,
        flush_every=1, fuse_rounds=1) reproduce the synchronous loop
        exactly; with staleness off, every pipelined configuration
        yields bit-identical history under the same seed. A record is
        appended EVERY round — convergence curves at full resolution,
        not subsampled to eval_every; eval fields only when evaluated.
        ``start_round`` continues a resumed run (see ``resume``)."""
        with span("fedmeta.run", start_round=start_round, rounds=rounds):
            return self._run(state, rounds, eval_every, eval_clients, log,
                             start_round)

    def _run(self, state, rounds, eval_every, eval_clients, log,
             start_round):
        stream = TaskStream(self.train_clients, self.clients_per_round,
                            self.support_frac, self.support_size,
                            self.query_size, self._rng)
        state = self._place_state(state)
        dp = self.placement()
        produced = {"r": start_round}   # prefetch-thread round cursor
        if self.pool_workers > 0:
            clients = self.train_clients
            self._pool = WorkerPool(lambda i: clients[i],
                                    workers=self.pool_workers,
                                    max_retries=self.pool_retries,
                                    task_timeout=self.task_timeout)

        def stage(k):
            # retry safety: a transiently failing stage() must not leak
            # partial stream draws (or breaker/participation state), or
            # the retry would see different tasks than the sync run
            entry = self._capture_rngs()
            try:
                if self._population_active:
                    args = self._stage_population(dp, produced["r"] + 1)
                else:
                    if self._pool is not None and k == 1:
                        # pre-warm the registry cache for the upcoming
                        # picks — peeked without consuming the stream,
                        # so staging stays bit-identical to the
                        # pool-less path
                        self._pool.map(
                            sorted({int(p) for p in self._peek_picks()}),
                            label=f"round {produced['r'] + 1} warm")
                    args = self._stage_block(stream, dp, k,
                                             produced["r"] + 1)
            except BaseException:
                self._restore_rngs(entry)
                raise
            produced["r"] += k
            if self.checkpoint_every:
                # rng states *after* this block = the states a resume
                # from its boundary round must start from
                self._rng_snaps[produced["r"]] = self._capture_rngs()
            return args

        evaluate = None
        if eval_every and eval_clients is not None:
            def evaluate(st):
                acc, _, loss = evaluate_meta(
                    self.algo, self.phi_tree(st), eval_clients,
                    support_frac=self.support_frac,
                    support_size=self.support_size,
                    query_size=self.query_size, seed=self.seed,
                    evaluator=self._evaluator)
                return {"eval_acc": acc, "eval_loss": loss}

        checkpoint = None
        if self.checkpoint_every and self.checkpoint_dir:
            checkpoint = lambda st, r: self.save_checkpoint(st, r)  # noqa: E731
        engine = AsyncRoundEngine(
            stage=stage, step=lambda st, a: self._step(st, *a),
            comm=self.comm, history=self.history, fused_step=self._fused,
            prefetch_depth=self.prefetch_depth,
            flush_every=self.flush_every, fuse_rounds=self.fuse_rounds,
            checkpoint=checkpoint,
            checkpoint_every=self.checkpoint_every,
            prefetch_retries=self.prefetch_retries)
        try:
            return engine.run(state, rounds, eval_every=eval_every,
                              evaluate=evaluate, log=log,
                              start_round=start_round)
        finally:
            if self._pool is not None:
                self._pool.close()   # no leaked worker threads, ever
                self._pool = None
