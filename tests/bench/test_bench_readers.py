"""The readers of the serving program's phase counters and of the
whole-state copies, on hand-made window contexts: the values they read,
and None where the program has no such counters or programs (a program
from before them, or a run without a trace)."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

import run  # noqa: E402
from xplane import Event, Trace  # noqa: E402

COUNTERS = ("queue_wait_ms.revise", "host_ms_per_dispatch.revise",
            "device_wait_ms_per_dispatch.revise",
            "reingest_ms_per_kedit.revise")
MS = 1_000_000  # ns


@pytest.fixture
def ctx():
    before = {"async.queue_wait_ns": 5 * MS, "async.admitted_edits": 10,
              "batch.batch_steps": 4, "batch.edits_applied": 100,
              "batch.take_ns": MS, "batch.stack_ns": MS,
              "batch.launch_ns": MS, "batch.adopt_ns": MS,
              "batch.sync_ns": MS, "batch.reingest_ns": MS}
    after = {"async.queue_wait_ns": 5 * MS + 40 * 12 * MS,  # 40 edits
             "async.admitted_edits": 50,
             "batch.batch_steps": 24,  # 20 dispatches
             "batch.edits_applied": 2100,  # 2,000 edits
             "batch.take_ns": MS + 20 * 2 * MS,
             "batch.stack_ns": MS + 20 * 5 * MS,
             "batch.launch_ns": MS + 20 * 1 * MS,
             "batch.adopt_ns": MS + 20 * 3 * MS,
             "batch.sync_ns": MS + 20 * 17 * MS,
             "batch.reingest_ns": MS + 90 * MS}
    mods = [Event("jit_stack_states(4)", 0, 2 * MS),
            Event("jit__batch_apply_edits_local(9)", 2 * MS, 20 * MS),
            Event("jit_unstack_state(5)", 20 * MS, 21 * MS),
            Event("jit_unstack_state(5)", 21 * MS, 22 * MS),
            Event("jit_stack_states(4)", 30 * MS, 32 * MS),
            Event("jit__batch_apply_edits_local(9)", 32 * MS, 50 * MS),
            Event("jit_unstack_state(5)", 50 * MS, 51 * MS),
            Event("jit_stack_states(4)", 90 * MS, 92 * MS)]  # past the end
    trace = Trace({"/device:TPU:0": []}, {"/device:TPU:0": mods}, [],
                  0, 60 * MS)
    return SimpleNamespace(before=before, after=after, trace=trace)


@pytest.mark.parametrize("name,value", [
    ("queue_wait_ms.revise", 12.0),
    ("host_ms_per_dispatch.revise", 11.0),
    ("device_wait_ms_per_dispatch.revise", 17.0),
    ("reingest_ms_per_kedit.revise", 45.0),
    ("state_copy_ms.revise", 3.5),  # (2 + 1 + 1 + 2 + 1) ms / 2 steps
])
def test_reads_the_window(ctx, name, value):
    assert run.reader(name)(ctx) == pytest.approx(value)


@pytest.mark.parametrize("name", COUNTERS)
def test_none_without_phase_counters(ctx, name):
    # a program from before the phase counters: only the older counts
    keep = ("async.admitted_edits", "batch.batch_steps",
            "batch.edits_applied")
    ctx.before = {k: ctx.before[k] for k in keep}
    ctx.after = {k: ctx.after[k] for k in keep}
    assert run.reader(name)(ctx) is None


@pytest.mark.parametrize("name", COUNTERS)
def test_none_without_work(ctx, name):
    ctx.after = dict(ctx.before)
    assert run.reader(name)(ctx) is None


def test_state_copy_none_without_trace_or_programs(ctx):
    read = run.reader("state_copy_ms.revise")
    mods = ctx.trace.modules["/device:TPU:0"]
    ctx.trace.modules["/device:TPU:0"] = [
        e for e in mods if "stack" not in e.name]  # eager per-leaf copies
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None
