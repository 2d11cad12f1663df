"""The traffic generator: the seed fixes every op, every seed serves the
same set of lengths and the same schedule of bursts, each op is valid when
it applies, the closed loop never runs out, and no document may outgrow its
capacity class."""
import json
import os
import sys
from collections import Counter

import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
sys.path.insert(0, BENCH)

from traffic import Plan  # noqa: E402


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


SEEDS = (1, 2 ** 33 + 5)


def make(name, seed, seconds=30, bursts=20, **over):
    """A plan; in the closed loop, each session's first ``bursts`` drawn."""
    p = Plan(dict(mix(name), **over), 50272, seed, seconds)
    if p.mix["loop"] == "closed":
        for i in range(len(p.sessions)):
            for _ in range(bursts):
                p.next_burst(i, "window")
    return p


@pytest.mark.parametrize("name", ["typing_suggest", "revise"])
def test_seed_fixes_the_plan(name):
    a, b = make(name, SEEDS[1]), make(name, SEEDS[1])
    assert [s.base for s in a.sessions] == [s.base for s in b.sessions]
    assert [s.ops for s in a.sessions] == [s.ops for s in b.sessions]
    c = make(name, SEEDS[0])
    assert [s.ops for s in a.sessions] != [s.ops for s in c.sessions]


@pytest.mark.parametrize("name", ["typing_suggest", "revise"])
def test_every_seed_serves_the_same_sizes(name):
    plans = [make(name, s) for s in SEEDS]
    lengths = [sorted(len(s.base) for s in p.sessions) for p in plans]
    assert lengths[0] == lengths[1]
    lo, hi = mix(name)["doc_len"]
    assert lengths[0][0] == lo and lengths[0][-1] == hi
    # the same number of edits at the same due times in every slot
    due = [[[op[0] for op in s.ops] for s in p.sessions] for p in plans]
    assert due[0] == due[1]


@pytest.mark.parametrize("name", ["typing_suggest", "revise"])
def test_schedule_is_the_same_for_every_seed(name):
    """Arrivals and burst sizes come from the mix's schedule_seed, not the
    run's seed: each session slot sends as many edits, at the same due
    times, under every seed, and another schedule_seed changes them."""
    a, b = (make(name, s) for s in SEEDS)
    sizes = lambda p: [len(s.ops) for s in p.sessions]
    assert sizes(a) == sizes(b)
    other = make(name, SEEDS[0], schedule_seed=mix(name)["schedule_seed"] + 1)
    assert sizes(other) != sizes(a)


def test_open_loop_schedule():
    m = mix("typing_suggest")
    p = make("typing_suggest", SEEDS[0])
    sched = p.schedule("window")
    assert all(0 <= due < 30 for due, _, _ in sched)
    assert [d for d, _, _ in sched] == sorted(d for d, _, _ in sched)
    # the offered rate is the mix's, within a Poisson draw's spread
    assert 0.6 * m["rate_edits_per_s"] * 30 <= len(sched) \
        <= 1.4 * m["rate_edits_per_s"] * 30
    kinds = Counter(op[2] for s in p.sessions for op in s.ops)
    assert kinds["insert"] > kinds["replace"]  # typing bursts dominate
    assert p.schedule("warm") and all(op[1] in ("warm", "window")
                                      for s in p.sessions for op in s.ops)
    # a session's bursts never overlap: its edits are at least a gap apart
    gap = m["burst_gap_ms"] / 1e3
    for s in p.sessions:
        dues = [op[0] for op in s.ops if op[1] == "window"]
        assert all(b - a >= gap - 1e-9 for a, b in zip(dues, dues[1:]))


def test_closed_loop_never_runs_out_and_keeps_its_prefix():
    short = make("revise", SEEDS[1], bursts=5)
    long = make("revise", SEEDS[1], bursts=400)
    for a, b in zip(short.sessions, long.sessions):
        assert b.ops[:len(a.ops)] == a.ops
    assert sum(len(s.ops) for s in long.sessions) > 30000


@pytest.mark.parametrize("name", ["typing_suggest", "revise"])
def test_ops_are_valid_when_they_apply(name):
    p = make(name, SEEDS[0])
    for s in p.sessions:
        ref = list(s.base)
        for _, _, kind, pos, tok in s.ops:
            assert 0 <= tok < 50272
            if kind == "insert":
                assert 0 <= pos <= len(ref)
                ref.insert(pos, tok)
            elif kind == "delete":
                assert 0 <= pos < len(ref)
                del ref[pos]
            else:
                assert 0 <= pos < len(ref)
                ref[pos] = tok
        assert ref == s.replay(len(s.ops)) == s.ref


@pytest.mark.parametrize("name", ["typing_suggest", "revise"])
def test_growth_guard(name):
    """An insert that would take a document past max_doc_len is sent as a
    replace: no document outgrows it at any point of its stream."""
    cap = mix(name)["doc_len"][1] + 2
    p = make(name, SEEDS[0], seconds=120, bursts=300, max_doc_len=cap,
             p_typing=1.0, rate_edits_per_s=40.0)
    for s in p.sessions:
        n = len(s.base)
        for _, _, kind, _, _ in s.ops:
            n += (kind == "insert") - (kind == "delete")
            assert n <= max(cap, len(s.base))
    assert any(len(s.ref) == cap for s in p.sessions)
