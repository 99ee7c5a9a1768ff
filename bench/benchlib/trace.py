"""The profiler trace of a run's window, reduced to what the per-layer
metrics read.

A TPU trace (`jax.profiler`, read back with `ProfileData`) has one plane
per chip, `/device:TPU:<n>`, whose line "XLA Modules" holds each
executable's run and whose line "XLA Ops" holds its operations, nested
(a `while` holds its body's operations). The host plane `/host:CPU`
holds the benchmark's own spans (`bench.*`) on the same clock. The
window is the span `bench.window`.

  busy      the union of the chip's module intervals inside the window
  self time an operation's time less that of the operations nested in it
  idle gap  a stretch of the window in which no module runs on the chip,
            named by the innermost `bench.*` span the host was in
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from collections import defaultdict

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def span(name: str):
    """A host span in the profiler's trace (costs nothing untraced)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """Starts and stops the profiler around the window, when asked to."""

    def __init__(self, directory: str | None):
        self.directory = directory
        self.running = False

    def start(self):
        if self.directory is None:
            return
        import jax
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        jax.profiler.start_trace(self.directory)
        self.running = True

    def stop(self):
        if self.running:
            import jax
            jax.profiler.stop_trace()
            self.running = False


@dataclasses.dataclass
class Op:
    chip: int
    name: str        # the HLO text of the operation
    start: float     # seconds from the window's start
    end: float
    self_s: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float                 # mean over chips
    modules: dict                 # chip -> [(start, end)] merged, seconds
    ops: list                     # [Op] inside the window
    host_spans: list              # [(name, start, end)] bench.* spans

    def op_self_seconds(self) -> dict:
        out = defaultdict(float)
        for op in self.ops:
            out[short_name(op.name)] += op.self_s
        return dict(out)


def short_name(hlo: str) -> str:
    """'%fusion.12 = f32[8,128]{1,0} fusion(...), ...' ->
    'fusion.12 fusion f32[8,128]': the operation, its kind (a custom
    call's target) and its result's shape, without layouts."""
    lhs, _, rhs = hlo.partition(" = ")
    lhs = lhs.strip().lstrip("%")
    rhs = re.sub(r"\{[^{}]*\}", "", rhs)
    m = re.search(r"^(.*?)\s([a-z][a-z0-9\-_]*)\(", rhs)
    shape, kind = (m.group(1).strip(), m.group(2)) if m else ("", "")
    target = re.search(r'custom_call_target="([^"]+)"', rhs)
    if target:
        kind = target.group(1)
    return " ".join(x for x in (lhs, kind, shape[:80]) if x)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _self_times(events):
    """events: [(start, end, name)] of one line, nested -> self times."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    selfs = [e[1] - e[0] for e in events]
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return events, selfs


def reduce_profile(profile) -> TraceSummary:
    """A `jax.profiler.ProfileData` -> the window's summary."""
    window = None
    host_spans = []
    devices = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW and window is None:
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name.startswith("bench."):
                        host_spans.append((ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = window

    def clip(s, e):
        return max(s, w0), min(e, w1)

    modules, ops = {}, []
    for chip, plane in sorted(devices.items()):
        mods, raw = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    s, e = clip(ev.start_ns, ev.end_ns)
                    if e > s:
                        mods.append((s, e))
            elif line.name == "XLA Ops":
                raw = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in line.events
                       if ev.end_ns > w0 and ev.start_ns < w1]
        modules[chip] = [((s - w0) / 1e9, (e - w0) / 1e9)
                         for s, e in _merge(mods)]
        events, selfs = _self_times(raw)
        for (s, e, name), sf in zip(events, selfs):
            cs, ce = clip(s, e)
            share = (ce - cs) / (e - s) if e > s else 1.0
            ops.append(Op(chip, name, (cs - w0) / 1e9, (ce - w0) / 1e9,
                          sf * share / 1e9))
    busy = [sum(e - s for s, e in iv) for iv in modules.values()]
    spans = [(n, (s - w0) / 1e9, (e - w0) / 1e9) for n, s, e in host_spans
             if e > w0 and s < w1]
    return TraceSummary(window_s=(w1 - w0) / 1e9, chips=len(devices),
                        busy_s=sum(busy) / len(busy), modules=modules,
                        ops=ops, host_spans=spans)


def load(directory: str) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(find_xplane(directory)))


def idle_gaps(summary: TraceSummary, chip: int | None = None) -> dict:
    """Idle seconds of the window by the host span the gap began in
    (the innermost `bench.*` span covering its start; 'outside spans'
    where none does)."""
    chip = min(summary.modules) if chip is None else chip
    gaps, t = [], 0.0
    for s, e in summary.modules[chip]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if summary.window_s > t:
        gaps.append((t, summary.window_s))
    out = defaultdict(float)
    for s, e in gaps:
        inner = [(ss, n) for n, ss, ee in summary.host_spans
                 if ss <= s < ee]
        name = max(inner)[1] if inner else "outside spans"
        out[name] += e - s
    return dict(out)


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The device operations that took most time (self time, summed over
    chips and divided by their number) and the longest idle gaps."""
    ops = sorted(summary.op_self_seconds().items(), key=lambda kv: -kv[1])
    gaps = sorted(idle_gaps(summary).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s / summary.chips] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in gaps[:top]]}
