"""Phase spans and time counters of the serving program (DESIGN.md §8).

``phase(stats, field, name, **ids)`` marks one phase of the serving loop
two ways at once:

* a profiler span, ``jax.profiler.TraceAnnotation(name, **ids)``: in any
  trace it lands on the trace's own clock, beside the device's programs,
  with its ids as event stats;
* an integer nanosecond counter, ``stats.<field>``, that the phase's
  elapsed ``time.perf_counter_ns()`` is added to on exit.

Both are always on. With no trace active a phase costs two clock reads and
a no-op annotation.

A counter takes the phase's own time: a phase nested in another phase of
the same ``stats`` object subtracts its time from the enclosing one, so the
counters of one owner partition the time its outermost phases cover. A
phase of another owner does not subtract (``AsyncStats.flush_ns`` holds
every ``BatchStats`` phase of the flush it covers).

Span names start with ``serve.``; the benchmark's own spans and program
names (``bench.*``, the edit step, the ``fused_step`` kernel) are matched
by name, so no span here may contain them.
"""
from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_open = threading.local()  # .stack: this thread's open phases, outermost first


class phase:
    """Context manager: one span and one counter for the code it covers."""

    __slots__ = ("stats", "field", "span", "start_ns", "inner_ns")

    def __init__(self, stats, field: str, name: str, **ids):
        self.stats = stats
        self.field = field
        self.span = TraceAnnotation(name, **ids)
        self.inner_ns = 0

    def __enter__(self) -> "phase":
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append(self)
        self.span.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        elapsed = time.perf_counter_ns() - self.start_ns
        self.span.__exit__(*exc)
        stack = _open.stack
        stack.pop()
        for outer in reversed(stack):
            if outer.stats is self.stats:
                outer.inner_ns += elapsed
                break
        setattr(self.stats, self.field,
                getattr(self.stats, self.field) + elapsed - self.inner_ns)
