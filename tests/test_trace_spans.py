"""The round driver's spans and the compile counter in the JAX
profiler's trace (DESIGN.md §19).

A tiny client-plane femnist trainer runs 3 rounds under
`jax.profiler.trace`; the host plane is read back with `ProfileData`:
every span is there, nested as documented, with its stats; compiles
happen in round 1 only; and tracing leaves the history bit-identical.
"""
import glob
import math

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import classification_loss
from repro.data.synth_femnist import make_femnist
from repro.federated.experiment import default_plan, make_trainer
from repro.models.paper import femnist_cnn
from repro.utils import trace

ROUNDS = 3
ROUND_CHILDREN = ("fedmeta.round.dispatch", "fedmeta.round.flush")


def _trainer(prefetch_depth):
    data = make_femnist(num_clients=8, num_classes=4, image_size=8,
                        mean_samples=12, seed=0)
    plan = default_plan("femnist", pipeline="client_plane",
                        clients_per_round=4, support_size=4, query_size=4,
                        num_clients=8, seed=0, flush_every=1,
                        prefetch_depth=prefetch_depth)
    model = femnist_cnn(num_classes=4, image_size=8, hidden=16)
    loss_fn, eval_fn = classification_loss(model.apply)
    tr = make_trainer(plan, "fomaml", loss_fn, eval_fn, data.clients)
    return tr, tr.init(jax.random.PRNGKey(0), model.init)


def _host_events(directory):
    """-> {line id: [(name, start_ns, end_ns, stats)]} of fedmeta.*"""
    path, = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("fedmeta.")]
            if evs:
                out[i] = evs
    return out


@pytest.fixture(scope="module", params=[0, 1], ids=["sync", "prefetch1"])
def traced(request, tmp_path_factory):
    """A traced and an untraced run of fresh trainers; the traced one
    records the bytes of every round's staged arguments."""
    depth = request.param
    tr, state = _trainer(depth)
    staged = []
    step = tr._step

    def recording_step(st, *args):
        staged.append(sum(x.nbytes for x in jax.tree.leaves(args)))
        return step(st, *args)

    tr._step = recording_step
    directory = str(tmp_path_factory.mktemp(f"trace{depth}"))
    with jax.profiler.trace(directory):
        tr.run(state, ROUNDS)
    plain, plain_state = _trainer(depth)
    plain.run(plain_state, ROUNDS)
    return dict(depth=depth, lines=_host_events(directory), staged=staged,
                history=tr.history, plain_history=plain.history)


def _main_line(lines):
    (main,) = [evs for evs in lines.values()
               if any(n == "fedmeta.run" for n, *_ in evs)]
    return main


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _named(evs, name):
    return [e for e in evs if e[0] == name]


def test_every_span_appears(traced):
    names = {n for evs in traced["lines"].values() for n, *_ in evs}
    want = {"fedmeta.run", "fedmeta.round", "fedmeta.round.stage",
            "fedmeta.round.sample", "fedmeta.round.put",
            "fedmeta.round.dispatch", "fedmeta.round.flush",
            trace.COMPILE_SPAN}
    if traced["depth"]:
        want.add("fedmeta.round.prefetch_wait")
    assert want <= names, want - names


def test_spans_nest_by_round(traced):
    main = _main_line(traced["lines"])
    (run,) = _named(main, "fedmeta.run")
    assert run[3] == {"start_round": 0, "rounds": ROUNDS}
    rounds = _named(main, "fedmeta.round")
    assert [e[3]["round"] for e in rounds] == list(range(1, ROUNDS + 1))
    assert all(e[3]["k"] == 1 and _inside(e, run) for e in rounds)
    by_round = {e[3]["round"]: e for e in rounds}
    staging = ("fedmeta.round.prefetch_wait" if traced["depth"]
               else "fedmeta.round.stage")
    for name in ROUND_CHILDREN + (staging,):
        evs = _named(main, name)
        assert sorted(e[3]["round"] for e in evs) == \
            list(range(1, ROUNDS + 1)), name
        assert all(_inside(e, by_round[e[3]["round"]]) for e in evs), name
    assert all(e[3]["rounds"] == 1
               for e in _named(main, "fedmeta.round.flush"))


def test_staging_spans_sit_on_the_staging_thread(traced):
    """Inline, staging nests in the round; prefetched, the producer
    thread opens its own stage spans with the same round stats."""
    lines = traced["lines"]
    main = _main_line(lines)
    (line,) = [evs for evs in lines.values()
               if _named(evs, "fedmeta.round.stage")]
    assert (line is main) == (traced["depth"] == 0)
    stages = {e[3]["round"]: e for e in _named(line, "fedmeta.round.stage")}
    assert sorted(stages) == list(range(1, ROUNDS + 1))
    for name in ("fedmeta.round.sample", "fedmeta.round.put"):
        evs = _named(line, name)
        assert sorted(e[3]["round"] for e in evs) == sorted(stages), name
        assert all(_inside(e, stages[e[3]["round"]]) for e in evs), name
    (sample, put) = (_named(line, "fedmeta.round.sample"),
                     _named(line, "fedmeta.round.put"))
    # sampling ends before the first device_put
    assert all(s[2] <= p[1] for s, p in zip(sample, put))


def test_put_bytes_are_the_staged_arrays(traced):
    puts = [e for evs in traced["lines"].values()
            for e in _named(evs, "fedmeta.round.put")]
    got = [e[3]["bytes"] for e in sorted(puts, key=lambda e: e[3]["round"])]
    assert got == traced["staged"] and all(b > 0 for b in got)


def test_compiles_happen_in_round_one_only(traced):
    main = _main_line(traced["lines"])
    (run,) = _named(main, "fedmeta.run")
    by_round = {e[3]["round"]: e for e in _named(main, "fedmeta.round")}
    marks = [e for evs in traced["lines"].values()
             for e in _named(evs, trace.COMPILE_SPAN) if _inside(e, run)]
    assert marks, "the first round compiles its step"
    assert all(_inside(m, by_round[1]) for m in marks)
    counts = [m[3]["n"] for m in marks]
    assert counts == sorted(counts) and len(set(counts)) == len(counts)
    dispatch = {e[3]["round"]: e[3]["compiles"]
                for e in _named(main, "fedmeta.round.dispatch")}
    # the counter as rounds 2 and 3 open their dispatch: all compiled
    assert dispatch[2] == dispatch[3] >= max(counts)


def _same(a, b):
    return a == b or (isinstance(a, float) and math.isnan(a)
                      and math.isnan(b))


def test_tracing_leaves_history_bit_identical(traced):
    h, p = traced["history"], traced["plain_history"]
    assert len(h) == len(p) == ROUNDS
    for rec, want in zip(h, p):
        assert rec.keys() == want.keys()
        assert all(_same(rec[k], want[k]) for k in rec), (rec, want)


def test_compile_counter_marks_cache_hits(tmp_path):
    """A persistent-cache retrieval announced before the compile event
    on the same thread marks that one count `cached`."""
    before = trace.compiles()
    with jax.profiler.trace(str(tmp_path)):
        jax.monitoring.record_event_duration_secs(
            trace.CACHE_RETRIEVAL_EVENT, 0.001)
        jax.monitoring.record_event_duration_secs(
            trace.BACKEND_COMPILE_EVENT, 0.0126, fun_name="f")
        jax.monitoring.record_event_duration_secs(
            trace.BACKEND_COMPILE_EVENT, 0.25, fun_name="g")
    assert trace.compiles() == before + 2
    marks = [e[3] for evs in _host_events(str(tmp_path)).values()
             for e in _named(evs, trace.COMPILE_SPAN)]
    assert marks == [{"n": before + 1, "ms": 13, "cached": 1},
                     {"n": before + 2, "ms": 250, "cached": 0}]


def test_compile_counter_counts_every_thread_once():
    """Compile events from many threads at once: no count is lost, and
    a cache retrieval marks only its own thread's next count."""
    import sys
    import threading

    threads, per_thread = 8, 200
    before = trace.compiles()
    start = threading.Barrier(threads)

    def compile_events(cached):
        start.wait(timeout=10)
        for _ in range(per_thread):
            if cached:
                jax.monitoring.record_event_duration_secs(
                    trace.CACHE_RETRIEVAL_EVENT, 0.0)
            jax.monitoring.record_event_duration_secs(
                trace.BACKEND_COMPILE_EVENT, 0.0, fun_name="f")
        assert getattr(trace._hit, "cached", 0) == 0

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(target=compile_events, args=(i % 2,))
                   for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert trace.compiles() == before + threads * per_thread
