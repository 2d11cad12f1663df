"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader is ``read(ctx) -> float | None``: ``ctx`` holds the window's
request records (host clock), the server's counters before and after the
window, and in a traced run the reduced trace (``bench/trace.py``) and the
work the window's edit dispatches could not do without (``bench/work.py``).
A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import math

import xplane as tr

EDIT_STEP = r"_batch_apply_edits_local"
FUSED_KERNEL = r"fused_step"
REFRESH_SPAN = "bench.suggest.refresh"


def p95_ms(values) -> float | None:
    v = sorted(values)
    if not v:
        return None
    x = v[max(math.ceil(0.95 * len(v)) - 1, 0)]
    return x * 1e3


def delta(ctx, key: str) -> float:
    return ctx.after[key] - ctx.before.get(key, 0)


def ratio(ctx, num: str, den: str, scale: float = 1.0) -> float | None:
    d = delta(ctx, den)
    return scale * delta(ctx, num) / d if d else None


def program_ms(ctx, pattern: str) -> float | None:
    """Mean device time of one execution of the programs matching
    ``pattern`` in the traced window (ms)."""
    if ctx.trace is None:
        return None
    runs = [e for evs in ctx.trace.modules.values()
            for e in tr.in_window(ctx.trace, tr.named(evs, pattern))]
    if not runs:
        return None
    return sum(e.dur for e in runs) / len(runs) / 1e6


def kernel_ns(ctx, pattern: str) -> float | None:
    if ctx.trace is None:
        return None
    evs = [e for line in ctx.trace.ops.values()
           for e in tr.in_window(ctx.trace, tr.named(line, pattern))]
    return sum(e.dur for e in evs) / len(ctx.trace.ops) if evs else None


def idle_share(ctx) -> float | None:
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - tr.busy_ns(ctx.trace) / ctx.trace.window_ns)


def spans(ctx, name: str) -> list:
    if ctx.trace is None:
        return []
    return [s for s in ctx.trace.spans if s.name == name
            and s.start >= ctx.trace.t0 and s.end <= ctx.trace.t1]
