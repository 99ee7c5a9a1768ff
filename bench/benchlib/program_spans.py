"""The program's own spans and compile markers in a traced run's window.

The program opens `fedmeta.*` host spans at the round driver's
boundaries and leaves one `fedmeta.compile` marker per executable it
compiles (`repro.utils.trace`). `trace.py` keeps only the benchmark's
`bench.*` spans, and a reader gets only `(summary, work, peaks)`, so
this module reads the run's trace file itself: the newest
`*.xplane.pb` under `bench/out/`, taken only when its `bench.window`
lasts exactly the summary's window. A stale trace or a hand-made
summary reads None.

  spans     the `fedmeta.*` events of the host line that holds
            `bench.window` (the thread that drives the rounds), in
            seconds from the window's start, with their stats
  compiles  the `fedmeta.compile` markers of every host line, likewise

`idle_by_span` names each idle stretch of the chip by the innermost
program span covering it (not by the span a gap began in, as the
breakdown does); idle time inside no program span is under None.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import sys
from collections import defaultdict
from pathlib import Path

from benchlib.trace import WINDOW, find_xplane

OUT_DIR = Path(__file__).resolve().parents[1] / "out"
PREFIX = "fedmeta."
COMPILE = "fedmeta.compile"
RUN = "fedmeta.run"
# the span names of each slice of the round driver's idle time; every
# other program span inside `fedmeta.run` is per-call overhead
STAGE = ("fedmeta.round.stage", "fedmeta.round.sample", "fedmeta.round.put",
         "fedmeta.round.prefetch_wait")
DISPATCH = ("fedmeta.round.dispatch",)
COUNTER_MODULE = "repro.utils.trace"


@dataclasses.dataclass
class ProgramTrace:
    window_ns: int
    spans: list      # [(name, start, end, stats)], seconds, driver line
    compiles: list   # [(start, stats)], seconds, every host line


_cache: dict = {}


def read_xplane(path: str) -> ProgramTrace | None:
    """The program's events of one trace file; None without a window."""
    from jax.profiler import ProfileData
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    lines = [list(line.events) for p in host for line in p.lines]
    window = next(((ev.start_ns, ev.end_ns, events) for events in lines
                   for ev in events if ev.name == WINDOW), None)
    if window is None:
        return None
    w0, w1, driver = window
    spans, compiles = [], []
    for events in lines:
        for ev in events:
            if not ev.name.startswith(PREFIX):
                continue
            start = (ev.start_ns - w0) / 1e9
            if ev.name == COMPILE:
                compiles.append((start, dict(ev.stats)))
            elif events is driver:
                spans.append((ev.name, start, (ev.end_ns - w0) / 1e9,
                              dict(ev.stats)))
    return ProgramTrace(round(w1 - w0), spans, compiles)


def load(summary) -> ProgramTrace | None:
    """The program's events of the run `summary` was reduced from."""
    try:
        path = find_xplane(str(OUT_DIR))
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = read_xplane(path)
    found = _cache[key]
    if found is None or found.window_ns != round(summary.window_s * 1e9):
        return None
    return found


def idle_by_span(modules: dict, spans: list, window_s: float) -> dict:
    """Idle seconds of the window by the innermost span covering each
    part of each idle stretch, averaged over the chips.

    modules: chip -> [(start, end)] merged busy intervals, seconds;
    spans: [(name, start, end, ...)] on one thread, so nested."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    cuts = sorted({t for s in spans for t in s[1:3]})
    out = defaultdict(float)
    for busy in modules.values():
        t = 0.0
        for s, e in list(busy) + [(window_s, window_s)]:
            if s > t:
                edges = [t] + cuts[bisect.bisect_right(cuts, t):
                                   bisect.bisect_left(cuts, s)] + [s]
                for a, b in zip(edges, edges[1:]):
                    out[_innermost(spans, a, b)] += (b - a) / len(modules)
            t = max(t, e)
    return dict(out)


def _innermost(spans, a, b):
    """The name of the latest-opened span covering [a, b], or None."""
    name = None
    for s in spans:
        if s[1] > a:
            break
        if s[2] >= b:
            name = s[0]
    return name


def idle_split(summary) -> dict | None:
    """The window's idle share (%) in the round driver's slices: stage,
    dispatch, the rest of `fedmeta.run` (run_overhead), and outside
    it; None where the trace holds no `fedmeta.run` span."""
    found = load(summary)
    if found is None or not any(s[0] == RUN for s in found.spans):
        return None
    idle = idle_by_span(summary.modules, found.spans, summary.window_s)
    split = dict.fromkeys(("stage", "dispatch", "run_overhead", "outside"),
                          0.0)
    for name, seconds in idle.items():
        key = ("outside" if name is None else "stage" if name in STAGE
               else "dispatch" if name in DISPATCH else "run_overhead")
        split[key] += seconds
    return {k: 100.0 * v / summary.window_s for k, v in split.items()}


def compiles_in_window(summary) -> int | None:
    """`fedmeta.compile` markers inside the window; None where the
    program's compile counter is not loaded in this process (a program
    without it leaves no marker, which must not read as 0)."""
    if COUNTER_MODULE not in sys.modules:
        return None
    found = load(summary)
    if found is None:
        return None
    return sum(1 for t, _ in found.compiles if 0.0 <= t <= summary.window_s)
