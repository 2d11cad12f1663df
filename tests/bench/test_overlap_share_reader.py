"""The reader of the scheduler's overlapped-dispatch counter on hand-made
window contexts: the share it reads, and None where the program has no
such counter or the window no dispatch."""
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

import run  # noqa: E402

NAME = "overlap_share.revise"


@pytest.fixture
def ctx():
    before = {"batch.batch_steps": 4, "batch.overlapped_dispatches": 3}
    after = {"batch.batch_steps": 24,  # 20 dispatches
             "batch.overlapped_dispatches": 18}  # 15 of them overlapped
    return SimpleNamespace(before=before, after=after, trace=None)


def test_reads_the_window(ctx):
    assert run.reader(NAME)(ctx) == pytest.approx(75.0)


def test_none_without_the_counter(ctx):
    # a program from before the counter: only the dispatch count
    ctx.before = {"batch.batch_steps": 4}
    ctx.after = {"batch.batch_steps": 24}
    assert run.reader(NAME)(ctx) is None


def test_none_without_dispatches(ctx):
    ctx.after = dict(ctx.before)
    assert run.reader(NAME)(ctx) is None
