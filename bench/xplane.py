"""Reduction of a profiler trace to device busy time, per-program and
per-kernel device time, host spans and idle gaps.

A traced run records an ``.xplane.pb`` with ``jax.profiler``. Device planes
(``/device:TPU:<i>``) carry one line of XLA operations and one of whole
programs ("XLA Modules"); the host plane carries the benchmark's own spans
(``bench.*``, ``jax.profiler.TraceAnnotation``) on the threads that opened
them. All times are nanoseconds on the trace's one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict  # device plane name -> [Event] of its operations line
    modules: dict  # device plane name -> [Event] of its programs line
    spans: list  # host spans whose name starts with the span prefix
    t0: float  # traced window
    t1: float

    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {len(paths)}")
    return paths[0]


def _events(line) -> list:
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def load(path: str, *, device_plane: str = r"^/device:TPU:\d+$",
         ops_line: str = r"^XLA Ops$", modules_line: str = r"^XLA Modules$",
         span_prefix: str = "bench.", start_mark: str = "bench.mark.start",
         end_mark: str = "bench.mark.end") -> Trace:
    """Read a trace. Planes and lines are picked by regular expression. The
    window runs from the start of the ``start_mark`` span to the end of the
    ``end_mark`` span; without them, over every device event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if re.search(device_plane, plane.name):
            for line in plane.lines:
                if re.search(ops_line, line.name):
                    ops[plane.name] = _events(line)
                elif re.search(modules_line, line.name):
                    modules[plane.name] = _events(line)
        for line in plane.lines:
            spans.extend(e for e in _events(line)
                         if e.name.startswith(span_prefix))
    if not ops:
        raise ValueError(f"no device plane matching {device_plane!r} with a "
                         f"{ops_line!r} line in {path}")
    marks = {e.name: e for e in spans if e.name in (start_mark, end_mark)}
    if start_mark in marks and end_mark in marks:
        t0, t1 = marks[start_mark].start, marks[end_mark].end
    else:
        every = [e for evs in ops.values() for e in evs]
        t0 = min(e.start for e in every)
        t1 = max(e.end for e in every)
    spans = sorted((e for e in spans if e.name not in marks),
                   key=lambda e: e.start)
    return Trace(ops, modules, spans, t0, t1)


def union(intervals, t0: float = float("-inf"),
          t1: float = float("inf")) -> list:
    """Merged, sorted (start, end) intervals, clipped to [t0, t1]."""
    out = []
    for s, e in sorted((max(a, t0), min(b, t1)) for a, b in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: list, b: list) -> list:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def busy_ns(tr: Trace) -> float:
    """Device busy time in the window, averaged over the device planes:
    the union of the intervals in which some operation ran."""
    per = [length(union(((e.start, e.end) for e in evs), tr.t0, tr.t1))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def named(events, pattern: str) -> list:
    """The events whose name matches ``pattern``. A pattern that matches
    nothing is an error: a renamed program or kernel must not read as 0."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e.name)]
    if not hit:
        raise KeyError(f"no trace event matches {pattern!r}")
    return hit


def in_window(tr: Trace, events) -> list:
    return [e for e in events if e.start >= tr.t0 and e.end <= tr.t1]


def device_time_in(tr: Trace, spans) -> float:
    """Device busy time (averaged over planes) that falls inside the
    union of ``spans``."""
    cover = union(((s.start, s.end) for s in spans), tr.t0, tr.t1)
    per = [length(intersect(union(((e.start, e.end) for e in evs),
                                  tr.t0, tr.t1), cover))
           for evs in tr.ops.values()]
    return sum(per) / len(per)


def top_ops(tr: Trace, k: int = 10) -> list:
    """The ``k`` operation names with the most device time in the window,
    as [name, seconds] (averaged over planes)."""
    total: dict = {}
    for evs in tr.ops.values():
        for e in in_window(tr, evs):
            total[e.name] = total.get(e.name, 0.0) + e.dur
    n = len(tr.ops)
    return [[name, ns / n / 1e9] for name, ns in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(tr: Trace, k: int = 10) -> list:
    """The ``k`` longest stretches of the first device plane with no
    operation running, each labelled with the innermost host span that
    covers its midpoint ("no span" where none does), as [label, seconds]."""
    evs = next(iter(tr.ops.values()))
    busy = union(((e.start, e.end) for e in evs), tr.t0, tr.t1)
    gaps, prev = [], tr.t0
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if tr.t1 > prev:
        gaps.append((prev, tr.t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) / 2
        cover = [sp for sp in tr.spans if sp.start <= mid <= sp.end]
        label = min(cover, key=lambda sp: sp.dur).name if cover else "no span"
        out.append([label, (e - s) / 1e9])
    return out


def describe(path: str, top: int = 25) -> dict:
    """Planes, lines and the most frequent event names of a trace: what to
    look at before writing a name pattern against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            names: dict = {}
            for e in line.events:
                c = names.setdefault(e.name, [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns / 1e6
            lines[line.name] = sorted(names.items(),
                                      key=lambda kv: -kv[1][1])[:top]
        out[plane.name] = lines
    return out
