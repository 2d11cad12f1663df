"""The trace reduction: interval arithmetic on hand-made events, and the
loader on a small trace recorded on the CPU (host spans, the window marks,
busy union, name matching, an error on a name that matches nothing)."""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

import xplane as tr  # noqa: E402
from xplane import Event, Trace  # noqa: E402


def test_union_merges_and_clips():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert tr.union([(0, 10)], 2, 4) == [(2, 4)]
    assert tr.union([(0, 1)], 2, 4) == []
    assert tr.length([(0, 3), (5, 9)]) == 7


def test_intersect():
    assert tr.intersect([(0, 4), (6, 10)], [(2, 7)]) == [(2, 4), (6, 7)]


@pytest.fixture
def made():
    ops = [Event("fusion.1", 0, 10), Event("fused_step", 5, 20),
           Event("fusion.1", 40, 50), Event("copy", 90, 100)]
    mods = [Event("jit__batch_apply_edits_local(3)", 0, 20),
            Event("jit_step(7)", 40, 50)]
    spans = [Event("bench.suggest.refresh", 30, 60),
             Event("bench.server.flush", 25, 80)]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": mods}, spans,
                 0, 100)


def test_busy_is_the_union(made):
    # [0, 20) + [40, 50) + [90, 100): overlapping ops count once
    assert tr.busy_ns(made) == 40


def test_device_time_in_spans(made):
    # spans cover [25, 80); device busy inside it is [40, 50)
    assert tr.device_time_in(made, made.spans) == 10


def test_named_and_missing(made):
    evs = made.ops["/device:TPU:0"]
    assert [e.name for e in tr.named(evs, r"fused_step")] == ["fused_step"]
    with pytest.raises(KeyError):
        tr.named(evs, r"no_such_kernel")


def test_top_ops_and_idle_gaps(made):
    assert tr.top_ops(made, 2) == [["fusion.1", 20 / 1e9],
                                   ["fused_step", 15 / 1e9]]
    gaps = tr.idle_gaps(made, 2)
    # the longest gap [50, 90) has its midpoint 70 in flush alone; the gap
    # [20, 40) has 30 in both spans, and the innermost (refresh) names it
    assert gaps[0] == ["bench.server.flush", 40 / 1e9]
    assert gaps[1] == ["bench.suggest.refresh", 20 / 1e9]


def test_load_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.mark.start"):
        pass
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.suggest.refresh"):
            f(x).block_until_ready()
        time.sleep(0.002)
    with jax.profiler.TraceAnnotation("bench.mark.end"):
        pass
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    # the CPU has no device plane: read the host thread that ran the
    # programs as the "device" line
    t = tr.load(path, device_plane=r"^/host:CPU$", ops_line=r"^python$")
    assert [s.name for s in t.spans] == ["bench.suggest.refresh"] * 3
    assert 0 < t.window_ns
    assert all(t.t0 <= s.start and s.end <= t.t1 for s in t.spans)
    busy = tr.busy_ns(t)
    assert 0 < busy <= t.window_ns
    runs = tr.named(t.ops["/host:CPU"], r"PjitFunction")
    assert len(tr.in_window(t, runs)) >= 3
    with pytest.raises(KeyError):
        tr.named(t.ops["/host:CPU"], r"_batch_apply_edits_local")
    with pytest.raises(ValueError):
        tr.load(path)  # no TPU plane on the CPU
