"""Suggestion decoding: continuation generation over an edited document's
incremental state (the paper's motivating scenario — an AI writing assistant
that "update[s] its suggestions in real time as a document is edited").

The serving problem: after each ``apply_edits`` the jit engine holds exact
per-layer caches for the *document*, but a greedy continuation ("suggestion")
still needs a standard decode loop — and restarting that loop from scratch
re-prefills the whole document per keystroke. This module closes the gap
with prefix reuse (DESIGN.md §5):

1. ``JitIncrementalEngine.export_kv`` gathers the slot buffer's cached
   ``k``/``v`` into sequence order — a ready-made decode KV cache. Columns
   the incremental passes never touched are bit-exact against a full
   forward; touched columns are float-close only (ΔT accumulation order).
2. ``SuggestionEngine.refresh`` re-prefills **only from the earliest
   invalidated position**: rows strictly before the earliest edited
   position id depend, by causal masking, only on other untouched rows, so
   their cache entries are reused verbatim (from the previous refresh's
   decode cache when one exists, else from the KV export). Rows at/after
   it are recomputed through ``models.transformer.prefill_step`` in ONE
   fixed-shape chunk (chunk lengths bucketed to powers of two).
3. The continuation itself is ``serving.decode.make_serve_step`` greedy
   steps — the ordinary continuous-batching inner loop.

Exactness contract (tests/test_suggest_differential.py): the suggestion
token sequence equals a from-scratch full-recompute decode oracle on the
edited document, for every prefix of a mixed insert/delete/replace stream —
including defrag and buffer-growth re-ingests, which drop all reuse.

The contract survives thresholded propagation (``delta_threshold > 0``,
DESIGN.md §10) unchanged: a sigma-delta-suppressed row is always at a
position id >= the earliest edited pid (causality), i.e. at/after the
``invalid_from`` / ``touched_from`` boundary — and every row at/after the
boundary is re-prefilled here through the EXACT transformer math, never
read from the (possibly drifted) engine caches. Reused prefix rows were
never touched by any incremental pass, so they carry no drift at any
threshold. Suggestions therefore stay oracle-token-exact for the served
tolerance (tests/test_delta_threshold.py).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.bucketing import next_pow2
from repro.common.precision import pinned_precision
from repro.configs.base import ArchConfig
from repro.models import transformer as T
from repro.serving.decode import greedy_continue, make_serve_step
from repro.serving.jit_engine import JitIncrementalEngine, JitState


class PositionHeadroomError(RuntimeError):
    """The continuation's position ids would run past the embedding pool —
    the caller must defragment (re-spread ids, which restores tail headroom)
    before refreshing the suggestion."""


@dataclass
class SuggestStats:
    refreshes: int = 0
    rebuilds: int = 0  # decode cache (re)built from the KV export
    prefill_rows_reused: int = 0  # rows served from cached prefix state
    prefill_rows_recomputed: int = 0  # real rows re-prefilled
    prefill_rows_launched: int = 0  # incl. bucket padding (fixed shapes)
    decode_steps: int = 0

    @property
    def prefill_rows_total(self) -> int:
        return self.prefill_rows_reused + self.prefill_rows_recomputed

    @property
    def reused_fraction(self) -> float:
        return self.prefill_rows_reused / max(self.prefill_rows_total, 1)


@dataclass
class _SuggestCache:
    """Per-document decode caches persisted across refreshes. Rows
    ``0..n-1`` of the cache arrays hold the document's sequence-ordered
    state as of the last refresh (suggestion rows beyond ``n`` are stale —
    the next refresh rewinds the length counter past them)."""

    caches: list
    tokens: np.ndarray  # [n] sequence-ordered, as of the last refresh
    positions: np.ndarray  # [n]
    n: int
    n_cap: int
    n_new_cap: int


class SuggestionEngine:
    """Greedy continuation decoding with edited-prefix reuse.

    One instance serves many documents (pass a distinct ``key`` per
    document to persist its decode cache across refreshes); jit caches for
    the prefill/decode steps are shared, keyed by shape — chunk lengths
    are bucketed to powers of two, so a capacity-``n_cap`` document compiles
    O(log n_cap) prefill shapes total.
    """

    def __init__(self, params: dict, cfg: ArchConfig, *, default_new: int = 8,
                 dtype=jnp.float32, on_cache_bytes=None):
        if cfg.pos not in ("learned", "sampled"):
            raise ValueError("suggestion serving expects absolute position ids")
        self.params = params
        self.cfg = cfg
        self.default_new = int(default_new)
        self.dtype = dtype
        # traced at the serving precision, like the engine whose exported
        # k/v rows a refresh reuses
        self._step = jax.jit(pinned_precision(
            make_serve_step(cfg, sample=False)))
        self._prefill = jax.jit(pinned_precision(
            lambda p, c, t, pos: T.prefill_step(p, cfg, t, c, pos)))
        self._cache: dict = {}
        # residency listener (the state store's budget accounting): called
        # with (key, nbytes) whenever a document's persisted decode cache is
        # stored or dropped — decode caches are device memory and count
        # toward the serving budget as SOFT state (re-prefillable)
        self._on_cache_bytes = on_cache_bytes
        self.stats = SuggestStats()

    # ------------------------------------------------------------- cache mgmt

    def cache_nbytes(self, key) -> int:
        """Device bytes held by a document's persisted decode cache (0 when
        none) — length counters included; the budget does not care which
        rows are live."""
        entry = self._cache.get(key)
        if entry is None:
            return 0
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(entry.caches))

    def cached_keys(self) -> list:
        """Keys with a persisted decode cache (leak tests / reconciliation)."""
        return list(self._cache)

    def _notify(self, key, nbytes: int) -> None:
        if self._on_cache_bytes is not None:
            self._on_cache_bytes(key, nbytes)

    def drop(self, key) -> None:
        """Forget a document's persisted decode cache (defrag re-spreads
        every position id, so nothing in it is reusable; the state store
        also drops caches under budget pressure — soft state, the next
        refresh rebuilds from the KV export)."""
        if self._cache.pop(key, None) is not None:
            self._notify(key, 0)

    def pos_headroom(self, last_pos: int) -> int:
        """How many continuation ids fit after ``last_pos``."""
        return int(self.params["embed"]["pos"].shape[0]) - 1 - int(last_pos)

    # ------------------------------------------------------------- refresh

    def refresh(self, engine: JitIncrementalEngine, state: JitState, *,
                key=None, n_new: Optional[int] = None,
                invalid_from: Optional[int] = None,
                export_invalid_from: Optional[int] = None,
                on_token=None) -> np.ndarray:
        """Recompute the greedy continuation of the document in ``state``.

        ``invalid_from`` — earliest *position id* edited since the last
        refresh of ``key`` (None = nothing changed); governs prefix reuse of
        the persisted decode cache. ``export_invalid_from`` — earliest
        position id touched by incremental passes since the document's last
        full forward (None = the state IS a full forward); governs reuse
        when the cache must be (re)built from the KV export (first refresh,
        or capacity change). Rows before the relevant boundary are reused;
        rows at/after it — whose values an edit may have changed, directly
        or through count renormalization / VQ code flips, or whose
        propagation a ``delta_threshold`` suppressed (DESIGN.md §10; such
        rows never sit before the boundary) — are re-prefilled
        through the decode path. ``on_token`` streams each decoded token as
        it is produced (see ``serving.decode.greedy_continue``). Returns the
        ``n_new`` greedy tokens."""
        n_new = self.default_new if n_new is None else int(n_new)
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        n_new_cap = next_pow2(n_new)
        n = int(state.n_real)
        if n < 1:
            raise ValueError("cannot suggest over an empty document")
        n_cap = int(state.tokens.shape[0])
        # Sequence ordering from the small host-side leaves; the heavy k/v
        # gather (export_kv) runs only when the decode cache must be rebuilt.
        # Same sort key as _export_kv_impl (both stable), so the row order
        # matches the export's on the rebuild path — garbage tail included.
        host_valid = np.asarray(state.valid)
        host_positions = np.asarray(state.positions)
        order = np.argsort(np.where(host_valid, host_positions,
                                    np.iinfo(np.int32).max), kind="stable")
        seq_tokens = np.asarray(state.tokens)[order]
        seq_positions = host_positions[order]
        last_pos = int(seq_positions[n - 1])
        if self.pos_headroom(last_pos) < n_new:
            raise PositionHeadroomError(
                f"{n_new} continuation ids after position {last_pos} exceed "
                f"the embedding pool of {self.params['embed']['pos'].shape[0]}"
                " — defragment the document first")

        def boundary(watermark: Optional[int]) -> int:
            # first sequence row whose position id the edits may have
            # invalidated; the last row is always recomputed so the refresh
            # yields last-token logits
            if watermark is None:
                return n - 1
            return int(np.searchsorted(seq_positions[:n], watermark, "left"))

        entry = self._cache.get(key) if key is not None else None
        if entry is not None and (entry.n_cap != n_cap
                                  or entry.n_new_cap != n_new_cap):
            entry = None
        if entry is not None:
            p = min(boundary(invalid_from), n - 1)
            # the reused prefix must be the exact rows the cache encodes
            if not (np.array_equal(entry.positions[:p], seq_positions[:p])
                    and np.array_equal(entry.tokens[:p], seq_tokens[:p])):
                p = 0
            caches = entry.caches
        else:
            p = min(boundary(export_invalid_from), n - 1)
            exp = engine.export_kv(state)
            caches = T.caches_from_kv(
                self.cfg, exp.k[:, None], exp.v[:, None],
                jnp.zeros((1,), jnp.int32),
                seq_len=n_cap + n_new_cap, dtype=self.dtype)
            self.stats.rebuilds += 1

        # -------- re-prefill rows [p_eff, n) in one bucketed chunk. The
        # bucket extends the chunk *downward* (recomputing extra reusable
        # rows) so every launched row is a real cache slot; when even the
        # full document underfills its bucket, the chunk covers the whole
        # exported buffer — the garbage tail rows land beyond the final
        # length counter, where attention never sees them.
        M = next_pow2(n - p)
        p_eff = n - M
        if p_eff < 0:
            p_eff, M = 0, n_cap
        caches = T.set_cache_length(caches, p_eff)
        chunk_t = jnp.asarray(seq_tokens[p_eff:p_eff + M])[None]
        chunk_p = jnp.asarray(seq_positions[p_eff:p_eff + M])[None]
        logits, caches = self._prefill(self.params, caches, chunk_t, chunk_p)
        caches = T.set_cache_length(caches, n)
        last_logits = logits[:, n - 1 - p_eff]  # [1, vocab]

        # -------- greedy continuation on fresh tail position ids
        gen_pos = jnp.asarray(
            last_pos + 1 + np.arange(n_new, dtype=np.int32))[None]
        toks, caches = greedy_continue(self._step, self.params, caches,
                                       last_logits, gen_pos,
                                       on_token=on_token)
        out = np.asarray(toks[0], np.int32)

        if key is not None:
            self._cache[key] = _SuggestCache(
                caches=caches, tokens=seq_tokens[:n].copy(),
                positions=seq_positions[:n].copy(), n=n, n_cap=n_cap,
                n_new_cap=n_new_cap)
            self._notify(key, self.cache_nbytes(key))
        self.stats.refreshes += 1
        self.stats.prefill_rows_reused += p_eff
        self.stats.prefill_rows_recomputed += n - p_eff
        self.stats.prefill_rows_launched += M
        self.stats.decode_steps += n_new - 1
        return out


def oracle_suggestion(params: dict, cfg: ArchConfig,
                      engine: JitIncrementalEngine, tokens, positions, valid,
                      n_new: int,
                      suggester: Optional[SuggestionEngine] = None
                      ) -> np.ndarray:
    """The from-scratch full-recompute decode oracle: ingest the padded slot
    buffers with a full forward, then decode the continuation with ZERO
    prefix reuse (``export_invalid_from=0`` re-prefills every row through
    the decode path). The differential harness compares ``SuggestionEngine``
    outputs against this token-for-token. Pass a reusable ``suggester`` to
    share jit caches across oracle calls."""
    # eager host copies: callers pass LIVE server host mirrors, which jax
    # reads asynchronously (and may zero-copy) — a later edit would race
    # the deferred ingest read (see batch_server._device_copy)
    state = engine.full_forward(jnp.asarray(np.array(tokens, copy=True)),
                                jnp.asarray(np.array(positions, copy=True)),
                                jnp.asarray(np.array(valid, copy=True)))
    s = suggester or SuggestionEngine(params, cfg)
    return s.refresh(engine, state, n_new=n_new, export_invalid_from=0)
