"""The one traffic generator: every mix is a data file under
``bench/traffic/`` that this module reads.

A mix describes editor sessions, one per document:

* ``sessions``, and ``doc_len`` ``[lo, hi]``: starting lengths, spread
  evenly over the range and dealt to the sessions in an order drawn from the
  seed (every seed serves the same set of lengths);
* ``loop``: ``"open"`` (bursts arrive per session as a Poisson process, at
  ``rate_edits_per_s`` in all, whether or not the server keeps up) or
  ``"closed"`` (each session sends its next burst the moment every edit of
  the last one is acknowledged, for as long as the run lasts);
* a burst is ``1 + Poisson(burst_extra_mean)`` edits, ``burst_gap_ms``
  apart. A share ``p_typing`` of bursts are typing (inserts at a cursor that
  persists for the session); the rest are revisions (``revise_mix`` of
  replace / delete / insert within ``revise_spread`` tokens of a point);
* ``schedule_seed``: the arrival times, burst sizes and burst kinds of each
  session slot come from this number, not from the run's seed, so every
  seed serves the same schedule; the run's seed deals the documents to the
  slots and draws every position and token;
* ``subscribe_tokens``: the length of each session's standing suggestion
  subscription (0 = none);
* ``warmup_s``: seconds of the same traffic served before the window;
* ``max_doc_len``: no document outgrows it: an insert that would is sent
  as a replace instead.

Each session draws from streams of its own, so the closed loop never runs
out and the first bursts of a session are the same however long a run is.
Everything derives from the seed; the program sees only the generated ops.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

OPS = ("replace", "delete", "insert")


@dataclass
class Session:
    doc_id: str
    base: list  # starting tokens
    rng: np.random.Generator  # positions and tokens: from the run's seed
    sched: np.random.Generator  # arrivals, sizes, kinds: from schedule_seed
    # ops in submission order, each (due_s, phase, kind, pos, tok); phase is
    # "warm" or "window", due_s counts from the start of that phase (open
    # loop) or is None (closed loop)
    ops: list = field(default_factory=list)
    ref: list = field(default_factory=list)  # the document after ``ops``
    cursor: int = 0

    def replay(self, n_ops: int) -> list:
        """The document after its first ``n_ops`` ops: the reference for
        the served tokens."""
        ref = list(self.base)
        for _, _, kind, pos, tok in self.ops[:n_ops]:
            _apply(ref, kind, pos, tok)
        return ref


def _apply(ref: list, kind: str, pos: int, tok: int) -> None:
    if kind == "insert":
        ref.insert(pos, tok)
    elif kind == "delete":
        del ref[pos]
    else:
        ref[pos] = tok


class Plan:
    """One run's traffic: the sessions with their documents and ops."""

    def __init__(self, mix: dict, vocab: int, seed: int, seconds: float):
        self.mix = mix
        self.vocab = int(vocab)
        self.seconds = float(seconds)
        rng = np.random.default_rng([int(seed), 0x7AFF1C])
        n = int(mix["sessions"])
        lo, hi = mix["doc_len"]
        lengths = np.linspace(lo, hi, n).round().astype(int)
        lengths = lengths[rng.permutation(n)]
        sched_seed = int(mix["schedule_seed"])
        self.sessions = []
        for i in range(n):
            base = [int(t) for t in rng.integers(0, self.vocab, lengths[i])]
            s = Session(f"doc{i}", base,
                        rng=np.random.default_rng([int(seed), 0x0B5, i]),
                        sched=np.random.default_rng([sched_seed, 0x5C4ED, i]),
                        ref=list(base))
            s.cursor = int(s.rng.integers(len(base) + 1))
            self.sessions.append(s)
        self.subscribe = int(mix.get("subscribe_tokens", 0))
        self.gap_s = float(mix["burst_gap_ms"]) / 1e3
        self.max_len = int(mix["max_doc_len"])
        if mix["loop"] == "open":
            for phase, length in (("warm", float(mix["warmup_s"])),
                                  ("window", self.seconds)):
                for i in range(n):
                    self._open_phase(i, phase, length)
        elif mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")

    # ------------------------------------------------------------ building

    def mean_burst(self) -> float:
        return 1.0 + float(self.mix["burst_extra_mean"])

    def _draw(self, s: Session) -> tuple:
        """The next burst's (size, typing) from the session's schedule."""
        size = 1 + int(s.sched.poisson(float(self.mix["burst_extra_mean"])))
        return size, bool(s.sched.random() < float(self.mix["p_typing"]))

    def _open_phase(self, i: int, phase: str, length: float) -> None:
        s = self.sessions[i]
        per_s = (float(self.mix["rate_edits_per_s"])
                 / (self.mean_burst() * len(self.sessions)))
        t = free_at = 0.0
        while True:
            t += float(s.sched.exponential(1.0 / per_s))
            if t >= length:
                return
            size, typing = self._draw(s)
            start = max(t, free_at)  # a session's bursts never overlap
            dues = [start + j * self.gap_s for j in range(size)]
            dues = [u for u in dues if u < length]
            free_at = start + size * self.gap_s
            for u, (kind, pos, tok) in zip(dues, self._burst_ops(
                    s, len(dues), typing)):
                s.ops.append((u, phase, kind, pos, tok))

    def next_burst(self, i: int, phase: str) -> tuple:
        """Closed loop: draw session ``i``'s next burst, append its ops and
        return their index range in ``ops``. Touches only that session, so
        each session's thread may call it for its own."""
        s = self.sessions[i]
        size, typing = self._draw(s)
        lo = len(s.ops)
        for kind, pos, tok in self._burst_ops(s, size, typing):
            s.ops.append((None, phase, kind, pos, tok))
        return lo, len(s.ops)

    def _burst_ops(self, s: Session, size: int, typing: bool) -> list:
        ref, rng = s.ref, s.rng
        ops = []
        if typing:
            for _ in range(size):
                cur = min(s.cursor, len(ref))
                tok = int(rng.integers(self.vocab))
                if len(ref) < self.max_len:
                    ref.insert(cur, tok)
                    ops.append(("insert", cur, tok))
                    s.cursor = cur + 1
                else:  # full: overtype the token before the cursor
                    pos = max(cur - 1, 0)
                    ref[pos] = tok
                    ops.append(("replace", pos, tok))
            return ops
        mix = self.mix["revise_mix"]
        p = np.array([mix[k] for k in OPS], float)
        spread = int(self.mix["revise_spread"])
        center = int(rng.integers(len(ref)))
        for _ in range(size):
            kind = OPS[int(rng.choice(3, p=p / p.sum()))]
            if (kind == "delete" and len(ref) <= 2 * spread) or (
                    kind == "insert" and len(ref) >= self.max_len):
                kind = "replace"
            pos = min(max(center + int(rng.integers(-spread, spread + 1)), 0),
                      len(ref) - (0 if kind == "insert" else 1))
            tok = int(rng.integers(self.vocab))
            _apply(ref, kind, pos, tok)
            if kind == "insert" and pos <= s.cursor:
                s.cursor += 1
            elif kind == "delete" and pos < s.cursor:
                s.cursor -= 1
            ops.append((kind, pos, tok))
            center = min(pos, len(ref) - 1)
        return ops

    # ------------------------------------------------------------ views

    def window_edits(self) -> int:
        return sum(1 for s in self.sessions for op in s.ops
                   if op[1] == "window")

    def schedule(self, phase: str) -> list:
        """Open loop: every op of ``phase`` as (due_s, session index, op
        index), in due order."""
        out = [(op[0], i, j) for i, s in enumerate(self.sessions)
               for j, op in enumerate(s.ops) if op[1] == phase]
        out.sort()
        return out
