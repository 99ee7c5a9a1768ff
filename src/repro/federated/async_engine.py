"""Async round engine: the pipelined host↔device round loop.

The synchronous driver (PR 3) pays three host↔device stalls per round:
host-side numpy task sampling, blocking array transfers, and a
``float(v)`` metrics readback that forces the device to drain before
the next round can even be sampled. This engine removes all three while
keeping the *math* of the round loop untouched (DESIGN.md §12):

  * **prefetch** — a background thread owns the trainer's
    ``TaskStream`` (data/federated.py) and stages the next
    ``prefetch_depth`` rounds' batches onto the device with
    ``jax.device_put`` while the current round computes. The stream is
    advanced sequentially on that one thread, so the batch sequence —
    and therefore the whole run — is identical to the synchronous
    loop's under a fixed seed. ``prefetch_depth=0`` is the synchronous
    degenerate case: no thread, batches staged inline.
  * **deferred metrics** — per-round metrics stay unread ``jax.Array``s
    (comm counters stay host-side round indices) in a pending list and
    are drained to ``history`` every ``flush_every`` rounds and at
    ``run()`` exit. No per-round ``float()`` sync; the records that
    come out are bit-identical, just materialized later.
  * **fused-K** — with ``fuse_rounds=K > 1`` the driver hands the step
    K rounds' batches as one stacked ``(K, ...)`` buffer and the
    trainer runs them in a single ``lax.scan`` over rounds (packed
    pipeline only). Blocks are split so every eval round lands on a
    block boundary — evaluation needs φ on the host mid-stream.

Staleness-aware aggregation (``StalenessConfig``) is the engine-level
answer to straggler clients: a configured fraction of each round's
clients return their meta-gradient ``delay`` rounds late — computed
against the φ they were dispatched with — and the server aggregates
the arrived gradients with their weight discounted by ``discount**s``
(s = rounds of staleness). The discounted weighting runs through the
same fused packed aggregation kernel as the fresh path (DESIGN.md §3),
so the hot path stays flat. The actual step-level wiring lives in
``core/fedmeta.make_packed_meta_train_step``; this module owns the
config and the per-round straggler pick.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.utils.trace import compiles, span

PREFETCH_THREAD_NAME = "repro-round-prefetch"
WORKER_THREAD_NAME = "repro-pool-worker"


class PrefetchError(RuntimeError):
    """The prefetch producer failed permanently. Raised at the
    consumer's ``get()`` with ``__cause__`` chained to the producer's
    original exception, so the failing frame's traceback survives the
    thread hop."""


class WorkerPoolError(PrefetchError):
    """A worker-pool task failed permanently (retries exhausted, task
    timeout, or dead pool). Same semantics as `PrefetchError`: the
    message names the failing work, and for task failures ``__cause__``
    chains the worker-frame exception across the thread hop."""


def call_with_retry(fn, *, max_retries: int, backoff: float,
                    stop: Optional[threading.Event] = None):
    """Run ``fn()`` with bounded exponential-backoff retries — the one
    retry loop the prefetcher and the worker pool share (PR 6's
    retry-with-backoff semantics: ``backoff · 2^attempt`` seconds
    between attempts; ``fn`` must be retry-safe).

    Returns ``(None, result, attempts)`` on success,
    ``(exc, None, attempts)`` after exhaustion, or ``None`` if ``stop``
    was set before an attempt started."""
    for attempt in range(max(0, max_retries) + 1):
        if stop is not None and stop.is_set():
            return None
        try:
            return (None, fn(), attempt + 1)
        except BaseException as exc:
            if attempt >= max_retries:
                return (exc, None, attempt + 1)
            time.sleep(backoff * (2 ** attempt))
    return None  # unreachable


@dataclasses.dataclass(frozen=True)
class StalenessConfig:
    """Simulated straggler clients with discount-weighted aggregation.

    Each round, ``fraction`` of the sampled clients are stragglers: the
    meta-gradient they computed against the *current* φ arrives only
    ``delay`` rounds later, by which point φ has moved on — exactly the
    asynchronous-FL staleness semantics. On arrival a stale gradient's
    aggregation weight is its original data-count weight times
    ``discount ** s`` (weight × γ^s, s = its actual rounds of
    staleness), and the round's effective weights are renormalized over
    the rows actually aggregated. Fresh rows have s = 0 and keep their
    full weight. The straggler pick per round is seeded (``seed``) and
    independent of the task stream, so enabling staleness never perturbs
    task sampling.

    ``jitter=True`` models heterogeneous stragglers: instead of every
    straggler arriving exactly ``delay`` rounds late, each straggler
    independently draws a per-round seeded delay s ∈ [0, delay] (0 =
    arrives within the round, i.e. effectively fresh) and rejoins after
    s rounds at weight w·γ^s. ``jitter=False`` is bitwise-identical to
    the fixed-delay behavior — the fixed path's code is untouched and
    the rng draws the same values (tests pin this).

    >>> cfg = StalenessConfig(delay=2, fraction=0.25, jitter=True)
    >>> strag, fresh, delays = cfg.pick(4, np.random.RandomState(0))
    >>> delays.shape == strag.shape and (delays <= 2).all()
    True
    """
    delay: int = 1          # s_max: rounds between ModelTraining and arrival
    fraction: float = 0.25  # fraction of each round's clients that straggle
    discount: float = 0.5   # γ: an arrived gradient weighs w * γ^s
    jitter: bool = False    # per-straggler random delay in [0, delay]
    seed: int = 0

    def __post_init__(self):
        if self.delay < 1:
            raise ValueError("staleness delay must be >= 1")
        if not 0.0 <= self.fraction < 1.0:
            raise ValueError("straggler fraction must be in [0, 1)")

    def num_stragglers(self, m: int) -> int:
        """Static per-round straggler count (static shapes keep the step
        jitted once); at least one client always stays fresh."""
        return max(0, min(m - 1, int(round(self.fraction * m))))

    def pick(self, m: int, rng: np.random.RandomState):
        """One round's straggler pick — sorted int32 index arrays.

        Returns ``(straggler_idx, fresh_idx)``, plus a per-straggler
        ``delays`` array when ``jitter`` is on. With jitter off the rng
        consumes exactly the draws it always did (the off-path stays
        bitwise-identical)."""
        k = self.num_stragglers(m)
        perm = rng.permutation(m)
        sel = (np.sort(perm[:k]).astype(np.int32),
               np.sort(perm[k:]).astype(np.int32))
        if not self.jitter:
            return sel
        return sel + (rng.randint(0, self.delay + 1,
                                  size=k).astype(np.int32),)


class Prefetcher:
    """Bounded background producer of staged round inputs.

    ``produce(k)`` performs the host half of a round block — sampling
    from the task stream and ``jax.device_put``-staging the arrays —
    and is only ever called from this one thread, in block order, so
    seeded streams advance exactly as they would synchronously. The
    queue holds at most ``depth`` staged blocks (double-buffered device
    slots at depth 1). Example::

        pf = Prefetcher(stage, sizes=[1, 1, 1], depth=2)
        try:
            for _ in range(3):
                staged = pf.get()       # blocks until produced
        finally:
            pf.close()                  # joins the thread, always

    Failure on either side releases the other:

      * a producer exception is re-raised in the consumer at the
        ``get()`` for the failed block, with the producer-frame
        traceback intact (``max_retries > 0`` wraps it in a
        ``PrefetchError`` naming the failed rounds, chained via
        ``__cause__``);
      * ``close()`` (consumer exception or normal exit) sets the stop
        flag, drains the queue so a blocked ``put`` can observe it, and
        joins the thread — no leaked threads when a step raises;
      * a ``get()`` that would otherwise block forever on a dead
        producer (thread exited without staging the requested block)
        raises instead of deadlocking — the stored producer error if
        there is one, a ``PrefetchError`` otherwise.

    ``max_retries`` bounds transient-failure retries per block: the
    producer re-calls ``produce(k)`` up to that many extra times with
    exponential backoff (``retry_backoff · 2^attempt`` seconds) before
    giving up. ``produce`` must therefore be retry-safe: a failed call
    must leave its seeded streams where they started (the trainers
    snapshot/restore their RNGs around staging). ``first_round`` only
    labels error messages — the round numbering a resumed run is at.

    Lock-order contract (see `WorkerPool` for the full ordering): the
    producer↔consumer handoff itself rides the queue and the stop
    Event; ``self._lock`` guards exactly one plain attribute — the
    stored producer error — and is a *leaf* lock: both sides take it
    only around the ``_error`` read/write, never around ``put``/``get``
    or any other blocking call.
    """

    def __init__(self, produce: Callable, sizes, depth: int, *,
                 max_retries: int = 0, retry_backoff: float = 0.05,
                 first_round: int = 1):
        self._produce = produce
        self._sizes = list(sizes)
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._max_retries = max(0, max_retries)
        self._retry_backoff = retry_backoff
        self._first_round = first_round
        # leaf lock for _error: written on the producer thread, read on
        # the consumer thread after observing producer death — the
        # handoff is otherwise unsynchronized (thread-unguarded-write)
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, name=PREFETCH_THREAD_NAME, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _wrap(self, exc, k, r, attempts):
        if self._max_retries == 0:
            return exc      # no retry facility: surface verbatim
        rounds = (f"round {r}" if k == 1 else
                  f"rounds {r}..{r + k - 1}")
        err = PrefetchError(
            f"prefetch producer failed staging {rounds} after "
            f"{attempts} attempt(s) (max_retries={self._max_retries} "
            f"exhausted): {type(exc).__name__}: {exc}")
        err.__cause__ = exc  # original traceback survives the hop
        return err

    def _stage_span(self, k, r):
        with span("fedmeta.round.stage", round=r):
            return self._produce(k)

    def _produce_with_retry(self, k, r):
        out = call_with_retry(lambda: self._stage_span(k, r),
                              max_retries=self._max_retries,
                              backoff=self._retry_backoff,
                              stop=self._stop)
        if out is None:
            return None
        exc, item, attempts = out
        if exc is not None:
            return (self._wrap(exc, k, r, attempts), None)
        return (None, item)

    def _run(self):
        r = self._first_round
        try:
            for k in self._sizes:
                if self._stop.is_set():
                    return
                item = self._produce_with_retry(k, r)
                if item is None:
                    return
                if item[0] is not None:
                    with self._lock:
                        self._error = item[0]
                    self._put(item)
                    return
                if not self._put(item):
                    return
                r += k
        except BaseException as exc:  # pragma: no cover - safety net
            with self._lock:
                self._error = exc
            self._put((exc, None))

    def get(self):
        while True:
            try:
                exc, item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if not self._thread.is_alive():
                    # the producer died without staging this block; the
                    # stored error (if any) beats a blind deadlock
                    with self._lock:
                        err = self._error
                    if err is not None:
                        raise err
                    raise PrefetchError(
                        "prefetch producer thread exited without "
                        "staging the requested block")
        if exc is not None:
            raise exc
        return item

    def close(self):
        self._stop.set()
        while True:  # unblock a producer waiting on a full queue
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)

    @property
    def alive(self) -> bool:
        return self._thread.is_alive()


class _PoolTask:
    """One queued unit of pool work.

    Publication protocol (audited, DESIGN.md §16): ``result`` and
    ``error`` are written by exactly one worker *before* ``done.set()``
    and read by the gather side only *after* ``done`` is observed set —
    the Event is the happens-before edge, so neither field needs a
    lock. ``started_at`` is the one deliberately racy field: the worker
    publishes it unsynchronized and the gather side polls it purely to
    arm the task-timeout clock; a stale read can only delay timeout
    detection by one 50 ms poll tick, never corrupt a result."""
    __slots__ = ("item", "result", "error", "started_at", "done")

    def __init__(self, item):
        self.item = item
        self.result = None
        self.error: Optional[BaseException] = None
        self.started_at: Optional[float] = None
        self.done = threading.Event()


class WorkerPool:
    """K persistent worker threads materializing client shards.

    The fault-tolerant generalization of the single prefetch producer
    (DESIGN.md §15): ``map(items)`` fans the items out to ``workers``
    threads running ``fn(item)`` — concurrent registry
    materialization — and blocks until every task completes, returning
    results in submission order. Each task gets the shared
    retry-with-backoff loop (`call_with_retry`, PR 6 semantics — ``fn``
    must be retry-safe), and the gather side enforces a per-task
    ``task_timeout`` measured from the moment a worker *starts* the
    task (queue wait does not count against it).

    Failure semantics mirror `PrefetchError`:

      * a task that exhausts its retries raises `WorkerPoolError` at
        ``map()`` naming the item and the caller's ``label`` (e.g. the
        round being staged), with the worker-frame exception chained
        via ``__cause__``;
      * a task exceeding ``task_timeout`` raises `WorkerPoolError`
        without waiting for the stuck worker;
      * ``map()`` on a pool whose workers have all died raises instead
        of deadlocking;
      * ``close()`` stops the workers, drains queued tasks (their
        waiters are released), and joins every thread — no leaked
        threads, whatever the consumer did.

    Example::

        pool = WorkerPool(lambda i: registry[i], workers=4,
                          max_retries=2)
        try:
            shards = pool.map([3, 17, 42], label="round 7")
        finally:
            pool.close()

    **Acquired-order contract** (the lock-ordering audit the
    ``thread-lock-order`` lint rule stubs; DESIGN.md §16). Three
    blocking primitives meet when the pool materializes registry
    shards: the gather side's per-task ``done`` Events, the registry's
    per-client in-flight Events, and ``ClientRegistry._lock``. The
    deadlock-free order is::

        gather (map): wait on task.done        — holding NO locks
        worker (fn):  registry.__getitem__
                        acquire _lock          — leaf: hash/cache ops
                                                 only, released before
                                                 ANY blocking call
                        wait on in-flight Event — lock NOT held
                        source.get(i)           — lock NOT held

    i.e. every Event wait is lock-free and the registry lock is a leaf
    acquired strictly *after* all Event-level blocking. The forbidden
    inversion — holding ``_lock`` while waiting on an in-flight Event
    or a pool gather — parks the only thread that could ``set()`` the
    Event behind the lock it needs, which is exactly the shape the
    lint rule flags.
    """

    def __init__(self, fn: Callable, workers: int = 2, *,
                 max_retries: int = 0, retry_backoff: float = 0.05,
                 task_timeout: Optional[float] = None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        self._fn = fn
        self._max_retries = max(0, max_retries)
        self._retry_backoff = retry_backoff
        self._task_timeout = task_timeout
        self._q: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._threads = [
            threading.Thread(target=self._work, daemon=True,
                             name=f"{WORKER_THREAD_NAME}-{i}")
            for i in range(workers)]
        for t in self._threads:
            t.start()

    def _work(self):
        while not self._stop.is_set():
            try:
                task = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            task.started_at = time.monotonic()
            out = call_with_retry(lambda: self._fn(task.item),
                                  max_retries=self._max_retries,
                                  backoff=self._retry_backoff,
                                  stop=self._stop)
            if out is None:      # stopped mid-retry
                task.error = WorkerPoolError("worker pool closed")
            elif out[0] is not None:
                task.error = out[0]
            else:
                task.result = out[1]
            task.done.set()

    def _fail(self, msg, cause=None) -> WorkerPoolError:
        err = WorkerPoolError(msg)
        if cause is not None:
            err.__cause__ = cause
        return err

    def map(self, items, label: str = "") -> list:
        """Materialize ``items`` concurrently; results in order."""
        tasks = [_PoolTask(it) for it in items]
        for t in tasks:
            self._q.put(t)
        where = f" for {label}" if label else ""
        out = []
        for t in tasks:
            while not t.done.wait(timeout=0.05):
                if self._task_timeout is not None and \
                        t.started_at is not None and \
                        time.monotonic() - t.started_at > \
                        self._task_timeout:
                    raise self._fail(
                        f"worker task {t.item!r}{where} exceeded the "
                        f"{self._task_timeout}s task timeout")
                if not any(th.is_alive() for th in self._threads):
                    raise self._fail(
                        f"worker pool died before task {t.item!r}"
                        f"{where} completed")
            if t.error is not None:
                raise self._fail(
                    f"worker pool failed materializing {t.item!r}"
                    f"{where} after {self._max_retries + 1} attempt(s) "
                    f"(max_retries={self._max_retries} exhausted): "
                    f"{type(t.error).__name__}: {t.error}", t.error)
            out.append(t.result)
        return out

    def close(self):
        self._stop.set()
        while True:              # release waiters of never-run tasks
            try:
                task = self._q.get_nowait()
            except queue.Empty:
                break
            task.error = WorkerPoolError("worker pool closed")
            task.done.set()
        for t in self._threads:
            t.join(timeout=10.0)

    @property
    def alive(self) -> bool:
        return any(t.is_alive() for t in self._threads)


def plan_blocks(rounds: int, eval_every: int, fuse: int,
                start: int = 0) -> list:
    """Round-block sizes covering rounds ``start + 1``..``rounds``: at
    most ``fuse`` rounds per block, and a block boundary at every eval
    round (and the final round) so evaluation always sees post-step φ
    on the host. ``start > 0`` is the resumed-run case: the plan picks
    up mid-schedule with the same absolute eval boundaries, so a
    resumed run's blocks are the uninterrupted plan's tail.

    >>> plan_blocks(10, 4, 3)   # eval rounds 4 and 8 end their blocks
    [3, 1, 3, 1, 2]
    >>> plan_blocks(10, 4, 3, start=4)
    [3, 1, 2]
    """
    fuse = max(1, fuse)
    if rounds <= start:
        return []
    bounds = {rounds}
    if eval_every:
        bounds.update(b for b in range(eval_every, rounds + 1, eval_every)
                      if b > start)
    blocks, r = [], start
    for b in sorted(bounds):
        seg = b - r
        while seg > 0:
            k = min(fuse, seg)
            blocks.append(k)
            seg -= k
        r = b
    return blocks


@dataclasses.dataclass
class AsyncRoundEngine:
    """The round driver shared by ``FederatedTrainer`` and
    ``FedAvgTrainer``. The trainer supplies the task-specific pieces;
    the engine owns pipelining, metric deferral and record cadence:

      stage(k)            host+device staging of the next k rounds'
                          inputs (called in stream order — on the
                          prefetch thread when ``prefetch_depth > 0``)
      step(state, staged) one jitted round; -> (state, metrics)
      fused_step          optional: (state, stacked-(k,...) staged) ->
                          (state, metrics with leading (k,) axis)
      comm                CommTracker (ticked per round by the engine)
      history             trainer's record list, appended at flush time
      checkpoint          optional (state, round) -> None hook, called
                          every ``checkpoint_every`` rounds at block
                          boundaries (after the pending metrics flush,
                          so a checkpointed history is never partial)
      prefetch_retries    bounded retry-with-backoff for transient
                          staging failures (Prefetcher max_retries)

    Example — a minimal pipelined driver (what both trainers' ``run``
    methods build)::

        engine = AsyncRoundEngine(stage=stage, step=step, comm=comm,
                                  history=history, prefetch_depth=2,
                                  flush_every=4)
        state = engine.run(state, rounds=100, eval_every=10,
                           evaluate=lambda st: {"eval_acc": ...})
    """
    stage: Callable
    step: Callable
    comm: object
    history: list
    fused_step: Optional[Callable] = None
    prefetch_depth: int = 0
    flush_every: int = 1
    fuse_rounds: int = 1
    checkpoint: Optional[Callable] = None
    checkpoint_every: int = 0
    prefetch_retries: int = 0

    def run(self, state, rounds: int, *, eval_every: int = 0,
            evaluate: Optional[Callable] = None, log: Callable = None,
            start_round: int = 0):
        fuse = self.fuse_rounds if self.fused_step is not None else 1
        blocks = plan_blocks(rounds, eval_every if evaluate else 0, fuse,
                             start=start_round)
        pending: list = []
        first = start_round + 1   # the current block's first round

        def flush():
            # the only host-device sync in the loop: float() on the
            # pending rounds' still-on-device metric arrays
            if not pending:
                return
            with span("fedmeta.round.flush", round=first,
                      rounds=len(pending)):
                for n, metrics, comm_rounds, eval_fields in pending:
                    rec = {"round": n,
                           **{k: float(v) for k, v in metrics.items()},
                           **self.comm.summary_at(comm_rounds)}
                    if eval_fields:
                        rec.update(eval_fields)
                    self.history.append(rec)
                    if log:
                        log(rec)
                pending.clear()

        prefetch = None
        if self.prefetch_depth > 0:
            prefetch = Prefetcher(self.stage, blocks, self.prefetch_depth,
                                  max_retries=self.prefetch_retries,
                                  first_round=start_round + 1)
        r = start_round
        last_ckpt = start_round
        try:
            for bk in blocks:
                first = r + 1
                with span("fedmeta.round", round=first, k=bk):
                    if prefetch:
                        with span("fedmeta.round.prefetch_wait", round=first):
                            staged = prefetch.get()
                    else:
                        with span("fedmeta.round.stage", round=first):
                            staged = self.stage(bk)
                    with span("fedmeta.round.dispatch", round=first,
                              compiles=compiles()):
                        if bk == 1:
                            state, metrics = self.step(state, staged)
                            per_round = [metrics]
                        else:
                            state, stacked = self.fused_step(state, staged)
                            per_round = [
                                jax.tree.map(lambda x, i=i: x[i], stacked)
                                for i in range(bk)]
                    for metrics in per_round:
                        r += 1
                        self.comm.tick()
                        eval_fields = None
                        if evaluate and eval_every and \
                                (r % eval_every == 0 or r == rounds):
                            with span("fedmeta.round.eval", round=first):
                                eval_fields = evaluate(state)
                        pending.append((r, metrics, self.comm.rounds,
                                        eval_fields))
                        # eval rounds already synced the device to read φ,
                        # so draining there is free
                        if eval_fields is not None or (
                                self.flush_every and
                                r % self.flush_every == 0):
                            flush()
                    if (self.checkpoint is not None and self.checkpoint_every
                            and r - last_ckpt >= self.checkpoint_every):
                        # flush first: the payload captures history up to
                        # and including round r, never a pending tail
                        flush()
                        with span("fedmeta.round.checkpoint", round=first):
                            self.checkpoint(state, r)
                        last_ckpt = r
            return state
        finally:
            if prefetch is not None:
                prefetch.close()
            flush()
