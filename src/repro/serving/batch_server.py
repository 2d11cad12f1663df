"""Multi-document request scheduler over the batched jit engine.

The serving model (ROADMAP north star: heavy concurrent traffic):

1. clients ``open_document`` (or ``open_documents`` for a fleet) — each
   document lives in a **slot buffer** padded up to a power-of-two capacity
   ``n_cap``: real tokens occupy slots with a ``valid`` mask and gapped
   position ids (paper §3.3), sequence order is the position-id order, and
   the host keeps the slot↔sequence mapping. Same-bucket documents ingest
   together through a batched full forward;
2. clients submit edits from the FULL algebra — ``submit_replace``,
   ``submit_insert``, ``submit_delete`` (or ``submit_edit`` with a
   ``core.edits.Edit``) — which queue per-document (FIFO) in *sequence*
   coordinates, exactly as an editor emits them;
3. ``step()`` runs ONE scheduling round: each ready document contributes a
   **typed bucket** — the longest same-op FIFO prefix of its queue, up to
   ``C`` edits, translated from sequence coordinates to slots at take time
   (inserts claim a free slot + a mid-gap position id; deletes release
   theirs) — and documents are grouped by ``(n_cap, C, R, op)``. Every
   group chunk is served by ONE fixed-shape ``batch_apply_edits`` dispatch;
   the op vector is data, so replace/insert/delete buckets share a single
   compiled step per ``(B, n_cap, C, R)`` — no per-op re-jit;
4. structural edits have two *scheduled* slow paths, both full-forward
   re-ingests at bucket boundaries: **defrag** when a gap is exhausted
   (position ids re-spread, paper: "akin to defragmentation") and **grow**
   when the slot buffer is full (``n_cap`` doubles — a re-jit at the new
   shape, amortized);
5. a document whose per-doc overflow flag trips gets a full-forward
   **fallback** (its batched slice is discarded) and its row capacity ``R``
   doubles — capped at ``n_cap``, at which point overflow is impossible —
   moving it to a bigger bucket whose first dispatch re-jits (the classic
   capacity-doubling / re-jit serving policy);
6. clients may ``submit_suggest`` a standing **suggestion subscription**:
   each scheduling round keeps a greedy continuation of the document fresh
   through ``repro.serving.suggest.SuggestionEngine`` (KV export + re-prefill
   from the earliest invalidated position, DESIGN.md §5). A newer edit for
   the same document invalidates its pending suggestion; the refresh waits
   until the edits apply and then reuses every cache row before the
   earliest edited position id;
7. with ``mesh=`` (``repro.launch.mesh.make_serving_mesh``) every dispatch
   shards its document axis across the mesh (DESIGN.md §6): batches are
   padded to a multiple of the mesh's batch axis and members are PLACED —
   each shard serves a contiguous row block, so the scheduler assigns
   heavy edit buckets to the lightest block (greedy LPT) and tracks the
   per-device dirty-slot imbalance in ``stats.mean_shard_imbalance``.
   Defrag / grow / overflow-fallback re-ingests and suggestion refreshes
   are per-document host-side slow paths and are untouched by sharding; a
   mesh of size 1 (or ``mesh=None``) is the pre-mesh scheduler bit-for-bit
   (tests/test_sharded_parity.py);
8. document state is a **tiered, budgeted resource** (DESIGN.md §7,
   ``repro.serving.state_store``): with ``device_budget_bytes=`` the fleet
   may exceed device memory — least-recently-touched documents evict to a
   host-RAM snapshot (warm) and, past ``host_budget_bytes=``, to disk
   (cold), then **rehydrate bit-exactly on next touch** (a pure re-upload,
   never a recompute). ``close_document`` ends a session and releases its
   slots, allocator and caches; ``pin``/``unpin`` exempt latency-critical
   documents from eviction; suggestion decode caches count toward the
   budget as soft state (droppable independently — the next refresh
   re-prefills from the KV export). Per-tier byte/doc counts and the
   eviction/rehydration counters live in ``BatchStats``.

Scheduler invariants (property-tested in tests/test_batch_scheduler.py):
every submitted edit is applied exactly once; all bucket capacities
(``n_cap``, ``C``, ``R``) are powers of two; per-document FIFO submission
order is preserved, so final token buffers equal the edit-replayed
reference under any interleaving of submits and flushes. A failed dispatch
(device OOM, interrupt) rolls the affected documents back to their
pre-take snapshots — host mirrors, slot maps, position allocator
(``PositionAllocator.snapshot``/``restore``) and queues — losing nothing.

Padding correctness: free slots are ``valid=False``, so the position-order
causal mask excludes them from every real row's context; their (garbage)
activations are maintained but unread. They can consume propagation slots,
which only makes overflow conservative, never wrong.

Known cost: each dispatch stacks members' full ``JitState`` into a batched
pytree and unstacks the result — O(total state size) copies per round, not
O(C). A persistent per-bucket arena (documents resident in stacked arrays,
edits scattered in place) would remove the copies; measured step-only
timings live in ``benchmarks/batch_scaling.run_jit_batched``.
"""
from __future__ import annotations

import os
from collections import deque
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import (
    restore_serving_document, save_serving_document,
)
from repro.common.bucketing import capacity_class, next_pow2
from repro.configs.base import ArchConfig
from repro.core.edits import Edit
from repro.core.positional import PositionAllocator
from repro.serving.batch_engine import (
    BatchedJitEngine, stack_states, unstack_state,
)
from repro.serving.latency import LatencyStats
from repro.serving.jit_engine import (
    JitState, OP_DELETE, OP_INSERT, OP_REPLACE, state_from_host,
    state_nbytes_for, state_to_host,
)
from repro.serving.state_store import StateStore
from repro.serving.suggest import (
    PositionHeadroomError, SuggestionEngine, SuggestStats,
)


_OPCODE = {"replace": OP_REPLACE, "insert": OP_INSERT, "delete": OP_DELETE}


def _device_copy(arr: np.ndarray):
    """Move a LIVE host mirror onto the device through an eager host copy.

    jax's CPU backend reads numpy inputs ASYNCHRONOUSLY (and may zero-copy
    them outright) — ``jnp.array``'s copy semantics do not guarantee the
    source buffer is consumed before the call returns. Handing a mutable
    mirror (``doc.tokens`` / ``doc.valid`` / ``doc.positions``) straight to
    ``full_forward`` therefore lets the NEXT take's host-side mutation race
    the deferred device read — observed as a re-ingest that "saw" inserts
    which the following dispatch then applied AGAIN: double-counted
    ``n_real``, garbage columns baked into every row's T, VQ code flips
    (caught by the sharded-serving benchmark's oracle leg). The numpy-level
    ``np.array(..., copy=True)`` completes before returning and the fresh
    buffer is never mutated, so whenever jax actually reads it the content
    is the call-time snapshot. Arrays freshly built per call (``np.stack``
    results) are safe without this."""
    return jnp.asarray(np.array(arr, copy=True))


@dataclass
class BatchStats:
    docs: int = 0
    edits_submitted: int = 0
    edits_applied: int = 0
    batch_steps: int = 0  # batched dispatches issued
    batched_docs: int = 0  # sum of dispatch group sizes
    overflows: int = 0
    full_forwards: int = 0  # ingests + overflow/defrag/grow re-ingests
    defrags: int = 0  # gap exhaustion -> position-id re-spread
    grows: int = 0  # slot buffer full -> capacity-class jump
    device_defrags: int = 0  # defrags served by the device-side
    # gather + re-spread path (no host mirror round-trip; DESIGN.md §9)
    device_grows: int = 0  # grows served by the device-side pad_state path
    # (no full-forward re-ingest — existing slots keep their bits)
    rejits: int = 0  # distinct dispatch shapes traced
    kernel_launches: int = 0  # device program launches on the edit path
    # (edit dispatches, ingests/re-ingests, device pads/gathers) — the
    # per-edit launch budget of the fused hot path
    suggest_refreshes: int = 0  # suggestion recomputes served
    suggest_invalidations: int = 0  # fresh suggestions staled by newer edits
    suggest_cached_hits: int = 0  # suggestions served from the cached
    # continuation without touching the prefill/dispatch path (the
    # watermarks were unchanged since the last refresh)
    # ---- latency SLOs (DESIGN.md §8): per-request admission-to-completion
    # histograms, recorded by the async front end (serving.async_server)
    edit_latency: LatencyStats = field(default_factory=LatencyStats)
    suggest_latency: LatencyStats = field(default_factory=LatencyStats)
    # ---- per-device dispatch balance (mesh>1 serving, DESIGN.md §6)
    sharded_dispatches: int = 0  # dispatches issued over a mesh of size > 1
    shard_imbalance_sum: float = 0.0  # sum over dispatches of (max-min)/max load
    # ---- tiered state residency (state_store, DESIGN.md §7). Byte and doc
    # counters are maintained by the StateStore and reconcile exactly
    # against a recount of the underlying objects
    # (tests/test_state_store.py::test_stats_reconcile).
    closes: int = 0  # close_document calls (docs stays = documents opened)
    bytes_hot: int = 0  # device-resident document states
    bytes_warm: int = 0  # host-RAM snapshots
    bytes_cold: int = 0  # on-disk spills
    bytes_suggest: int = 0  # device-resident suggestion decode caches (soft)
    docs_hot: int = 0
    docs_warm: int = 0
    docs_cold: int = 0
    evictions: int = 0  # hot -> warm demotions
    spills: int = 0  # warm -> cold demotions
    rehydrations: int = 0  # warm/cold -> hot re-uploads (bit-exact)
    rollback_rebuilds: int = 0  # void -> hot full-forward rebuilds (rollback
    # corner: the pre-take copy was consumed by a mid-take re-ingest)
    state_touches: int = 0  # device-state reads routed through the store
    hot_hits: int = 0  # touches served without a rehydration/rebuild
    # ---- cross-process migration (fleet serving, DESIGN.md §11)
    exports: int = 0  # export_document calls (doc handed off to a snapshot)
    imports: int = 0  # import_document calls (doc adopted from a snapshot)

    @property
    def mean_batch(self) -> float:
        return self.batched_docs / max(self.batch_steps, 1)

    @property
    def traced_shapes(self) -> int:
        """Distinct compiled dispatch shapes this server has traced — the
        quantity the ragged capacity classes exist to bound (a long mixed
        stream must stay within a fixed shape budget,
        tests/test_mixed_edit_streams.py). Alias of ``rejits`` under the
        name the benchmarks report."""
        return self.rejits

    @property
    def kernel_launches_per_edit(self) -> float:
        """Edit-path device program launches per applied edit. The fused
        hot path's first-class wall-clock proxy: one launch per dispatch,
        amortized over its whole bucket, with slow paths (re-ingests,
        device pads/gathers) surfacing as fractional overhead."""
        return self.kernel_launches / max(self.edits_applied, 1)

    @property
    def hot_hit_rate(self) -> float:
        """Fraction of device-state touches served from the hot tier — the
        tiered store's first-class benchmarked quantity
        (benchmarks/state_churn.py). 1.0 = the budget never forced a
        rehydration."""
        return self.hot_hits / max(self.state_touches, 1)

    @property
    def mean_shard_imbalance(self) -> float:
        """Mean per-dispatch dirty-slot imbalance across mesh shards:
        0.0 = perfectly balanced, 1.0 = some device received all the work
        while another idled. The scheduler's balanced placement keeps this
        low; it is the first-class benchmarked quantity of sharded serving
        (benchmarks/sharded_serving.py)."""
        return self.shard_imbalance_sum / max(self.sharded_dispatches, 1)


@dataclass
class _BatchDoc:
    doc_id: str
    tokens: np.ndarray  # [n_cap] int32 slot buffer, host-side source of truth
    valid: np.ndarray  # [n_cap] bool
    positions: np.ndarray  # [n_cap] int32 (gapped ids; free slots: sentinel)
    slots: list  # sequence index -> slot (the host's order oracle)
    free: list  # free slot indices
    n_cap: int
    row_capacity: int  # per-document R; doubles on overflow
    allocator: PositionAllocator  # sequence-ordered gapped position ids
    state: Optional[JitState]  # device state at padded shape (None = evicted)
    state_epoch: int = 0  # bumped on every content-CHANGING state replacement
    # (dispatch adoption, re-ingest) but NOT on rehydration, which re-uploads
    # identical bits — the rollback path uses it to tell the two apart
    pending: deque = field(default_factory=deque)  # FIFO of (op, pos, tok)
    n_virtual: int = 0  # length after every queued edit applies
    # ---- suggestion serving (DESIGN.md §5)
    suggestion: Optional[np.ndarray] = None  # last refreshed continuation
    suggest_n: int = 0  # standing request length (0 = no subscription)
    suggest_fresh: bool = False  # suggestion matches the current doc + queue
    suggest_serial: int = 0  # bumped per real refresh (NOT per cached hit);
    # the async front end uses it to detect which subscriptions advanced
    invalid_from: Optional[int] = None  # min pid edited since last refresh
    touched_from: Optional[int] = None  # min pid touched since last ingest

    @property
    def n(self) -> int:  # real length
        return len(self.slots)

    def seq_tokens(self) -> np.ndarray:
        return self.tokens[np.asarray(self.slots, np.int64)]

    def seq_positions(self) -> np.ndarray:
        return self.positions[np.asarray(self.slots, np.int64)]


class BatchServer:
    """Full-edit-algebra serving for many documents over one vmapped engine."""

    def __init__(self, params: dict, cfg: ArchConfig, *, edit_capacity: int = 8,
                 row_capacity: int = 64, max_batch: int = 8,
                 min_doc_capacity: int = 16, use_patch_kernel: bool = False,
                 use_fused_kernel: bool = True,
                 delta_threshold: float = 0.0,
                 capacity_class_step: int = 4, device_grow: bool = True,
                 device_defrag: bool = True,
                 pos_pool: Optional[int] = None, mesh=None,
                 batch_axis: str = "data",
                 device_budget_bytes: Optional[int] = None,
                 host_budget_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None):
        """The fused ragged hot path (DESIGN.md §9) is ON by default:
        ``use_fused_kernel`` routes each layer's patch + requantize through
        one ``fused_step`` Pallas launch; ``capacity_class_step`` spaces the
        document capacity classes (4 = one compiled step serves a 4× range
        of lengths; 2 = the legacy power-of-two lattice); ``device_grow`` /
        ``device_defrag`` serve the structural slow paths on-device
        (``pad_state`` / ``gather_slots``) instead of host re-ingests. Set
        all four to their legacy values (False/2/False/False) to reproduce
        the pre-fused scheduler.

        ``delta_threshold`` is the served tolerance (sigma-delta tier,
        DESIGN.md §10): 0.0 (default) serves bit-exactly like the ungated
        stack; > 0 lets code-flipped rows whose hidden state drifted less
        than the threshold propagate nothing. Suppressed rows always sit at
        position ids >= the earliest edited pid (causal masking — exactly
        the rows the ``invalid_from`` / ``touched_from`` watermarks already
        cover), so suggestion refreshes re-prefill every possibly-drifted
        row through the exact decode path and stay oracle-TOKEN-exact at
        any threshold; only ``logits()`` served straight from engine state
        carries the bounded drift. Every engine this server builds (the
        base engine and each per-(C, R) bucket re-jit) shares the one
        threshold — the served tolerance is a server-level contract, not a
        per-document knob."""
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        if capacity_class_step < 2:
            raise ValueError("capacity_class_step must be >= 2")
        self.cfg = cfg
        self.C = next_pow2(edit_capacity)
        self.R = next_pow2(row_capacity)
        self.max_batch = max_batch
        self.min_doc_capacity = next_pow2(min_doc_capacity)
        self.use_patch_kernel = use_patch_kernel
        self.use_fused_kernel = use_fused_kernel
        self.delta_threshold = float(delta_threshold)
        self.capacity_class_step = capacity_class_step
        self.device_grow = device_grow
        self.device_defrag = device_defrag
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.pos_pool = pos_pool or (cfg.pos_pool if cfg.pos_pool else cfg.max_seq)
        base = BatchedJitEngine(params, cfg, edit_capacity=self.C,
                                row_capacity=self.R,
                                use_patch_kernel=use_patch_kernel,
                                use_fused_kernel=use_fused_kernel,
                                delta_threshold=self.delta_threshold,
                                mesh=mesh, batch_axis=batch_axis)
        if base.n_shards > max_batch:
            raise ValueError(
                f"serving mesh batch axis of {base.n_shards} exceeds "
                f"max_batch={max_batch} — every dispatch must give each "
                "device at least one document row")
        if max_batch % base.n_shards != 0:
            raise ValueError(
                f"max_batch={max_batch} is not a multiple of the serving "
                f"mesh's {base.n_shards}-way batch axis — a full chunk "
                "would pad past the max_batch cap")
        self.n_shards = base.n_shards
        self._weights = base.weights
        self._engines: dict[tuple[int, int], BatchedJitEngine] = {
            (self.C, self.R): base}
        self._shapes_seen: set = set()
        self.docs: dict[str, _BatchDoc] = {}
        self.stats = BatchStats()
        self._sugg: Optional[SuggestionEngine] = None
        self._params = params
        # streaming hook (serving.async_server): when set, every REAL
        # suggestion refresh calls ``on_suggest_token(doc_id, serial, token)``
        # per decoded token, as the decode loop produces it — cached-hit
        # fast paths do not re-stream tokens the subscriber already has
        self.on_suggest_token = None
        # True while step() is inside its take/dispatch section: host mirrors
        # of a peeled document run AHEAD of its device state there, so
        # snapshots the store captures mid-round are flagged inconsistent
        # (in-process rehydration is unaffected; fleet failover refuses to
        # adopt them and falls back to re-opening from tokens, DESIGN.md §11)
        self._in_round = False
        # tiered residency (DESIGN.md §7): budget=None still tracks bytes
        # and tiers — accounting is always on, eviction only under a budget
        self.store = StateStore(
            docs=self.docs, stats=self.stats,
            drop_suggest=self._drop_suggest_cache, reingest=self._reingest,
            device_budget_bytes=device_budget_bytes,
            host_budget_bytes=host_budget_bytes, spill_dir=spill_dir,
            in_round=lambda: self._in_round)

    def _drop_suggest_cache(self, doc_id: str) -> None:
        """Release one document's suggestion decode cache (the store's
        soft-state reclamation hook; the suggester's listener reports the
        freed bytes back to the store)."""
        if self._sugg is not None:
            self._sugg.drop(doc_id)

    @property
    def suggester(self) -> SuggestionEngine:
        """The (lazily built) suggestion engine shared by every document.
        Its per-document decode caches report their device bytes to the
        state store — soft state under the serving budget."""
        if self._sugg is None:
            self._sugg = SuggestionEngine(
                self._params, self.cfg,
                on_cache_bytes=self.store.note_suggest_bytes)
        return self._sugg

    @property
    def suggest_stats(self) -> SuggestStats:
        return self.suggester.stats

    # ------------------------------------------------------------- engines

    def engine(self, edit_capacity: int, row_capacity: int) -> BatchedJitEngine:
        """The per-capacity-bucket engine (cached; shares weight stacks and
        the serving mesh)."""
        key = (edit_capacity, row_capacity)
        if key not in self._engines:
            self._engines[key] = BatchedJitEngine(
                {}, self.cfg, edit_capacity=edit_capacity,
                row_capacity=row_capacity,
                use_patch_kernel=self.use_patch_kernel,
                use_fused_kernel=self.use_fused_kernel,
                delta_threshold=self.delta_threshold, mesh=self.mesh,
                batch_axis=self.batch_axis, _weights=self._weights)
        return self._engines[key]

    def _count_shape(self, shape: tuple) -> None:
        if shape not in self._shapes_seen:
            self._shapes_seen.add(shape)
            self.stats.rejits += 1

    def padded_cap(self, n: int) -> int:
        """The capacity class serving an ``n``-slot document: the smallest
        ``min_doc_capacity * step^k >= n``. All documents in a class share
        one padded shape — and therefore one compiled step per (B, C, R) —
        with valid/n_real masks carrying the real length (ragged
        execution, DESIGN.md §9)."""
        return capacity_class(n, self.min_doc_capacity,
                              self.capacity_class_step)

    def _padded_batch(self, chunk_len: int) -> int:
        """Dispatch batch sizes are padded up to a power of two (capped at
        ``max_batch``) so each capacity bucket compiles O(log max_batch)
        shapes instead of one per observed group size — then rounded up to a
        multiple of the serving mesh's batch axis, the shard_map divisibility
        contract (each device takes a contiguous ``B_pad / n_shards`` block
        of document rows)."""
        b = min(next_pow2(chunk_len), self.max_batch)
        n = self.n_shards
        b = max(b, n)
        return -(-b // n) * n

    def _place_rows(self, weights: list, B_pad: int) -> tuple[list, list]:
        """Balanced placement of dispatch members onto the padded batch rows.

        Each mesh shard serves the contiguous row block
        ``[s*B_pad/n, (s+1)*B_pad/n)``, so WHERE a document lands decides
        which device does its dirty-slot work. Greedy longest-processing-time
        assignment: heaviest bucket first onto the lightest non-full shard —
        the classic 4/3-approximation to makespan, plenty for C-bounded
        bucket weights. Returns ``(rows, loads)``: ``rows[r]`` is the member
        index occupying padded row ``r`` (None = filler row carrying an
        empty edit bucket), ``loads[s]`` the per-shard dirty-slot totals.
        With a single shard the placement is the identity — the pre-mesh
        dispatch layout, bit-for-bit."""
        n = self.n_shards
        if n == 1:
            rows = list(range(len(weights)))
            rows += [None] * (B_pad - len(weights))
            return rows, [sum(weights)]
        per = B_pad // n
        blocks: list[list] = [[] for _ in range(n)]
        loads = [0] * n
        order = sorted(range(len(weights)), key=lambda i: (-weights[i], i))
        for i in order:
            s = min((j for j in range(n) if len(blocks[j]) < per),
                    key=lambda j: (loads[j], len(blocks[j]), j))
            blocks[s].append(i)
            loads[s] += weights[i]
        rows = []
        for blk in blocks:
            rows.extend(blk)
            rows.extend([None] * (per - len(blk)))
        return rows, loads

    def _note_balance(self, loads: list) -> None:
        if self.n_shards > 1:
            self.stats.sharded_dispatches += 1
            hi = max(loads)
            self.stats.shard_imbalance_sum += (hi - min(loads)) / max(hi, 1)

    @property
    def _pos_sentinel(self) -> int:
        # Free slots point at the last pool embedding: always in-bounds for
        # the gather, >= every allocated id, and masked out by valid anyway.
        return self.pos_pool - 1

    # ------------------------------------------------------------- documents

    def open_document(self, doc_id: str, tokens: Sequence[int]) -> None:
        self.open_documents({doc_id: tokens})

    def open_documents(self, items: dict) -> None:
        """Ingest a fleet at once: documents sharing a capacity bucket are
        run through ONE ``batch_full_forward`` dispatch (chunked like
        edits)."""
        prepared = []
        for doc_id, tokens in items.items():
            if doc_id in self.docs:
                raise KeyError(f"document {doc_id!r} already open")
            n = len(tokens)
            if n < 1:
                raise ValueError("empty document")
            toks = np.asarray(tokens, np.int32)
            if toks.size and not (0 <= toks.min() and toks.max() < self.cfg.vocab):
                raise ValueError(
                    f"document {doc_id!r} has tokens outside vocab of "
                    f"{self.cfg.vocab}")
            n_cap = self.padded_cap(n)
            alloc = PositionAllocator(n, self.pos_pool)
            padded = np.zeros(n_cap, np.int32)
            padded[:n] = toks
            valid = np.zeros(n_cap, bool)
            valid[:n] = True
            positions = np.full(n_cap, self._pos_sentinel, np.int32)
            positions[:n] = alloc.snapshot()
            prepared.append((doc_id, padded, valid, positions, n, n_cap, alloc))
        eng = self.engine(self.C, self.R)
        groups: dict[int, list] = {}
        for p in prepared:
            groups.setdefault(p[5], []).append(p)
        for n_cap, members in sorted(groups.items()):
            for lo in range(0, len(members), self.max_batch):
                chunk = members[lo:lo + self.max_batch]
                B_pad = self._padded_batch(len(chunk))
                # admission control BEFORE the ingest dispatch: evict LRU
                # residents (suggestion caches first, then hot states) until
                # the chunk's states fit the device budget
                self.store.admit(
                    len(chunk) * state_nbytes_for(n_cap, eng.L, eng.meta))
                # ingest work scales with real length: balance it per shard
                rows, loads = self._place_rows([c[4] for c in chunk], B_pad)
                row_of = [chunk[i] if i is not None else chunk[0] for i in rows]
                toks = np.stack([c[1] for c in row_of])
                vals = np.stack([c[2] for c in row_of])
                poss = np.stack([c[3] for c in row_of])
                bstate = eng.batch_full_forward(
                    jnp.asarray(toks), jnp.asarray(poss), jnp.asarray(vals))
                self._count_shape(("full", B_pad, n_cap))
                self.stats.kernel_launches += 1
                self._note_balance(loads)
                for b, i in enumerate(rows):
                    if i is None:
                        continue
                    doc_id, padded, valid, positions, n, n_cap, alloc = chunk[i]
                    doc = _BatchDoc(
                        doc_id=doc_id, tokens=padded, valid=valid,
                        positions=positions, slots=list(range(n)),
                        free=list(range(n_cap - 1, n - 1, -1)), n_cap=n_cap,
                        row_capacity=min(self.R, n_cap), allocator=alloc,
                        state=unstack_state(bstate, b), n_virtual=n)
                    self.docs[doc_id] = doc
                    self.store.register(doc)
                    self.stats.docs += 1
                    self.stats.full_forwards += 1

    def close_document(self, doc_id: str) -> None:
        """End a session: release the document's slot rows, allocator,
        device/warm/cold state and suggestion caches. The inverse of
        ``open_document`` — leak-free under open→edit→close churn
        (tests/test_state_store.py::test_close_document_no_leak). Pending
        (unflushed) edits are discarded with the session."""
        doc = self.docs.pop(doc_id)  # KeyError for unknown ids
        self._drop_suggest_cache(doc_id)  # listener zeroes its byte account
        self.store.close(doc)
        doc.pending.clear()
        doc.suggestion = None
        self.stats.closes += 1

    def pin(self, doc_id: str) -> None:
        """Exempt a latency-critical document from eviction (rehydrating it
        now if needed, so a pinned document is always dispatch-ready). Its
        suggestion decode cache stays evictable — soft state."""
        if doc_id not in self.docs:
            raise KeyError(doc_id)
        self.store.pin(doc_id)

    def unpin(self, doc_id: str) -> None:
        self.store.unpin(doc_id)

    def evict(self, doc_id: str, tier: str = "warm") -> str:
        """Force-demote a document's state to ``"warm"`` (host RAM) or
        ``"cold"`` (disk). Its next touch — an edit dispatch, suggestion
        refresh or logits read — rehydrates it transparently and
        bit-exactly. Mostly a test/benchmark hook; production eviction is
        the budget's job. Returns the resulting tier."""
        return self.store.demote(self.docs[doc_id], tier)

    def tier(self, doc_id: str) -> str:
        """Residency tier of an open document: "hot", "warm" or "cold"."""
        if doc_id not in self.docs:
            raise KeyError(doc_id)
        return self.store.tier(doc_id)

    # ------------------------------------------------------------- submits

    def _check_tok(self, tok: int) -> None:
        if not 0 <= tok < self.cfg.vocab:
            raise ValueError(f"token {tok} outside vocab of {self.cfg.vocab}")

    def _stale(self, doc: _BatchDoc) -> None:
        """A newer edit for the document invalidates its suggestion."""
        if doc.suggest_fresh:
            doc.suggest_fresh = False
            self.stats.suggest_invalidations += 1

    def _touch(self, doc: _BatchDoc, pid: int) -> None:
        """Record an applied edit's position id in the invalidation
        watermarks (earliest-invalidated-position tracking, DESIGN.md §5).
        The same watermark covers sigma-delta-suppressed columns
        (``delta_threshold > 0``): causal masking confines every propagated
        OR suppressed row to position ids >= the earliest edited pid, so
        the min-over-edited-pids here is already the min over
        possibly-drifted rows (DESIGN.md §10)."""
        pid = int(pid)
        doc.invalid_from = (pid if doc.invalid_from is None
                            else min(doc.invalid_from, pid))
        doc.touched_from = (pid if doc.touched_from is None
                            else min(doc.touched_from, pid))

    def submit_replace(self, doc_id: str, pos: int, tok: int) -> None:
        doc = self.docs[doc_id]
        if not 0 <= pos < doc.n_virtual:
            raise IndexError(
                f"pos {pos} out of range for doc of length {doc.n_virtual}")
        self._check_tok(tok)
        doc.pending.append(("replace", int(pos), int(tok)))
        self._stale(doc)
        self.stats.edits_submitted += 1

    def submit_insert(self, doc_id: str, pos: int, tok: int) -> None:
        """Insert ``tok`` before sequence index ``pos`` (``pos == n``
        appends). Positions refer to the sequence state after every
        previously queued edit applies, exactly like an edit script."""
        doc = self.docs[doc_id]
        if not 0 <= pos <= doc.n_virtual:
            raise IndexError(
                f"insert pos {pos} out of range for doc of length {doc.n_virtual}")
        self._check_tok(tok)
        doc.pending.append(("insert", int(pos), int(tok)))
        doc.n_virtual += 1
        self._stale(doc)
        self.stats.edits_submitted += 1

    def submit_delete(self, doc_id: str, pos: int) -> None:
        doc = self.docs[doc_id]
        if not 0 <= pos < doc.n_virtual:
            raise IndexError(
                f"delete pos {pos} out of range for doc of length {doc.n_virtual}")
        if doc.n_virtual <= 1:
            raise ValueError("cannot delete the last remaining token")
        doc.pending.append(("delete", int(pos), 0))
        doc.n_virtual -= 1
        self._stale(doc)
        self.stats.edits_submitted += 1

    def submit_edit(self, doc_id: str, e: Edit) -> None:
        """Submit a ``core.edits.Edit`` (op/pos/token) as queued traffic."""
        if e.op == "replace":
            self.submit_replace(doc_id, e.pos, e.token)
        elif e.op == "insert":
            self.submit_insert(doc_id, e.pos, e.token)
        else:
            self.submit_delete(doc_id, e.pos)

    def pending_count(self) -> int:
        return sum(len(d.pending) for d in self.docs.values())

    # ------------------------------------------------------- snapshot/rollback

    def _snapshot(self, doc: _BatchDoc) -> tuple:
        return (doc.tokens.copy(), doc.valid.copy(), doc.positions.copy(),
                list(doc.slots), list(doc.free), doc.n_cap, doc.row_capacity,
                doc.allocator.snapshot(), doc.state, doc.state_epoch,
                deque(doc.pending), doc.n_virtual, doc.invalid_from,
                doc.touched_from, doc.suggest_fresh)

    def _restore(self, doc: _BatchDoc, snap: tuple) -> None:
        (doc.tokens, doc.valid, doc.positions, doc.slots, doc.free, doc.n_cap,
         doc.row_capacity, alloc_ids, state, epoch, doc.pending,
         doc.n_virtual, doc.invalid_from, doc.touched_from,
         doc.suggest_fresh) = snap
        doc.allocator.restore(alloc_ids)
        # Device-state rollback is residency-aware and NEVER raises (the
        # except path restores many docs in a row — one failure must not
        # strand the rest). Three cases:
        # 1. epoch unchanged — the device-state CONTENT was never replaced
        #    (at most evicted and/or rehydrated, both bit-preserving), and
        #    the store's accounting already matches wherever it lives now;
        # 2. a mid-take re-ingest (grow/defrag) replaced the content, but
        #    the snapshot still references the exact pre-take state —
        #    re-adopt it (the store recounts bytes and discards the
        #    superseded copy);
        # 3. the doc entered the take evicted (snapshot state is None) and a
        #    mid-take re-ingest consumed its warm/cold copy — the restored
        #    mirrors are the only source of truth. Mark the doc void: the
        #    next touch rebuilds it with a full forward (the same semantics
        #    as any re-ingest slow path), where admission/device failures
        #    are ordinary and recoverable.
        if epoch == doc.state_epoch:
            pass
        elif state is not None:
            self.store.set_hot(doc, state)
        else:
            self.store.mark_void(doc)

    # ------------------------------------------------------------- scheduling

    def _take_bucket(self, doc: _BatchDoc):
        """Pop the longest same-op FIFO prefix (up to C) into a typed edit
        bucket, translating sequence coordinates to slots as each edit is
        peeled — so every queued position means "the sequence as all earlier
        edits left it", matching edit-script semantics. Host mirrors
        (tokens/valid/positions/slot map/allocator) are updated here; the
        device catches up at dispatch. Returns (op_kind, arrays, count)."""
        kind = doc.pending[0][0]
        slot_a = np.full(self.C, -1, np.int32)
        tok_a = np.zeros(self.C, np.int32)
        pos_a = np.zeros(self.C, np.int32)
        op_a = np.full(self.C, _OPCODE[kind], np.int32)
        i = 0
        if kind == "replace":
            # Same-slot conflicts stay queued for the next round (a scatter
            # bucket holds one write per slot; distinct-slot replaces
            # commute, so later ones may still ship this round). Scanning
            # stops at the first structural op — replaces do NOT commute
            # across an insert/delete.
            taken: set[int] = set()
            kept: list = []
            while doc.pending and i < self.C:
                if doc.pending[0][0] != "replace":
                    break
                _, pos, tok = doc.pending.popleft()
                s = doc.slots[pos]
                if s in taken:
                    kept.append(("replace", pos, tok))
                    continue
                taken.add(s)
                slot_a[i] = s
                tok_a[i] = tok
                pos_a[i] = doc.positions[s]
                doc.tokens[s] = tok
                self._touch(doc, doc.positions[s])
                i += 1
            for item in reversed(kept):
                doc.pending.appendleft(item)
        elif kind == "insert":
            while doc.pending and i < self.C:
                if doc.pending[0][0] != "insert":
                    break
                _, pos, tok = doc.pending[0]
                need_grow = not doc.free
                need_defrag = not doc.allocator.can_insert_at(pos)
                if need_grow or need_defrag:
                    if i > 0:
                        break  # flush the partial bucket first; the re-ingest
                    if need_grow:  # below rebuilds device state from hosts
                        self._grow(doc)
                    if need_defrag:
                        self._defrag(doc)
                    if not doc.allocator.can_insert_at(pos):
                        raise RuntimeError(
                            f"position pool of {doc.allocator.pool_size} cannot "
                            f"host a document of length {doc.n + 1}")
                doc.pending.popleft()
                pid = doc.allocator.insert_at(pos)
                s = doc.free.pop()
                doc.slots.insert(pos, s)
                doc.tokens[s] = tok
                doc.valid[s] = True
                doc.positions[s] = pid
                slot_a[i] = s
                tok_a[i] = tok
                pos_a[i] = pid
                self._touch(doc, pid)
                i += 1
        else:  # delete
            while doc.pending and i < self.C:
                if doc.pending[0][0] != "delete":
                    break
                _, pos, _tok = doc.pending.popleft()
                s = doc.slots.pop(pos)
                doc.allocator.delete_at(pos)
                doc.valid[s] = False
                pos_a[i] = doc.positions[s]
                slot_a[i] = s
                doc.free.append(s)  # earliest reuse is the NEXT dispatch
                self._touch(doc, doc.positions[s])
                i += 1
        return kind, (slot_a, tok_a, pos_a, op_a), i

    def step(self) -> int:
        """One scheduling round: edit dispatches, then stale suggestion
        refreshes. Returns the number of edits applied."""
        ready = [d for d in self.docs.values() if d.pending]
        if not ready:
            self._refresh_suggestions()
            return 0
        takes = []  # (doc, kind, arrays, count)
        undone: dict[int, tuple] = {}  # id(doc) -> (doc, snapshot)
        applied = 0
        self._in_round = True
        try:
            for d in ready:
                snap = self._snapshot(d)
                undone[id(d)] = (d, snap)
                kind, arrays, count = self._take_bucket(d)
                if count == 0:
                    self._restore(d, snap)
                    undone.pop(id(d))
                    continue
                takes.append((d, kind, arrays, count))
            groups: dict[tuple, list] = {}
            for t in takes:
                groups.setdefault(
                    (t[0].n_cap, self.C, t[0].row_capacity, t[1]),
                    []).append(t)
            for (n_cap, C, R, kind), members in sorted(groups.items(),
                                                       key=lambda kv: kv[0]):
                for lo in range(0, len(members), self.max_batch):
                    chunk = members[lo:lo + self.max_batch]
                    applied += self._dispatch(chunk, n_cap, C, R, kind)
                    for t in chunk:
                        undone.pop(id(t[0]), None)
        except Exception:
            # a failed take (pool exhausted mid-bucket) or dispatch (device
            # OOM, interrupt) must not lose edits: every doc not yet served
            # rolls back to its pre-take snapshot (host mirrors, slot map,
            # allocator ids, queue — its device state was never replaced)
            for d, snap in undone.values():
                self._restore(d, snap)
            raise
        finally:
            self._in_round = False
        self._refresh_suggestions()
        return applied

    def flush(self) -> int:
        """Drain every queue; returns total edits applied. Stale suggestion
        subscriptions are refreshed too — also when there were no edits to
        drain (the subscribe-then-flush flow)."""
        total = 0
        while self.pending_count():
            total += self.step()
        self._refresh_suggestions()  # no-op when every subscription is fresh
        return total

    def _dispatch(self, chunk: list, n_cap: int, C: int, R: int,
                  kind: str) -> int:
        eng = self.engine(C, R)
        docs = [t[0] for t in chunk]
        buckets = [t[2] for t in chunk]
        counts = [t[3] for t in chunk]
        # transparent rehydration on touch: every chunk member must be hot
        # for the stacked dispatch — warm/cold members re-upload their
        # snapshots (bit-exact), protected from each other's admissions
        keep = frozenset(d.doc_id for d in docs)
        for d in docs:
            self.store.ensure_hot(d, keep=keep)
        # pad to a pow2 batch (multiple of the mesh's batch axis) with copies
        # of doc 0 carrying empty edit buckets (all -1): no-op slices whose
        # output is discarded. Members are placed to balance dirty-slot work
        # across the contiguous per-shard row blocks.
        B_pad = self._padded_batch(len(chunk))
        rows, loads = self._place_rows(counts, B_pad)
        empty = (np.full(C, -1, np.int32), np.zeros(C, np.int32),
                 np.zeros(C, np.int32), np.zeros(C, np.int32))
        row_buckets = [buckets[i] if i is not None else empty for i in rows]
        states = [docs[i].state if i is not None else docs[0].state
                  for i in rows]
        slot = jnp.asarray(np.stack([b[0] for b in row_buckets]))
        tok = jnp.asarray(np.stack([b[1] for b in row_buckets]))
        pos = jnp.asarray(np.stack([b[2] for b in row_buckets]))
        batched = stack_states(states)
        if kind == "replace":
            new_state, overflow = eng.batch_apply_replaces(batched, slot, tok)
        elif kind == "insert":
            new_state, overflow = eng.batch_apply_inserts(batched, slot, tok,
                                                          pos)
        else:
            new_state, overflow = eng.batch_apply_deletes(batched, slot)
        overflow = np.asarray(overflow)
        self.stats.batch_steps += 1
        self.stats.batched_docs += len(chunk)
        # all three op kinds share one compiled step per (B, n_cap, C, R):
        # the op vector is data, so `kind` is NOT part of the traced shape
        self._count_shape(("edit", B_pad, n_cap, C, R))
        self.stats.kernel_launches += 1
        self._note_balance(loads)
        applied = 0
        for b, i in enumerate(rows):
            if i is None:
                continue
            doc = docs[i]
            applied += counts[i]
            self.stats.edits_applied += counts[i]
            if overflow[b]:
                self._fallback_full_forward(doc)
            else:
                self.store.set_hot(doc, unstack_state(new_state, b))
        return applied

    # ------------------------------------------------------------ slow paths

    def _reingest(self, doc: _BatchDoc) -> None:
        """Rebuild device state from the host mirrors (one full forward)."""
        eng = self.engine(self.C, self.R)
        # admit the replacement state up front (a grown buffer is bigger
        # than the one it replaces; an evicted doc brings wholly new bytes)
        new_bytes = state_nbytes_for(doc.n_cap, eng.L, eng.meta)
        resident = (self.store.nbytes(doc.doc_id)
                    if self.store.tier(doc.doc_id) == "hot" else 0)
        self.store.admit(max(new_bytes - resident, 0),
                         keep=frozenset((doc.doc_id,)))
        state = eng.full_forward(_device_copy(doc.tokens),
                                 _device_copy(doc.positions),
                                 _device_copy(doc.valid))
        self.store.set_hot(doc, state)
        # the state is a from-scratch full forward again: every exported
        # column is trustworthy for suggestion KV reuse
        doc.touched_from = None
        self.stats.full_forwards += 1
        self.stats.kernel_launches += 1
        self._count_shape(("full", doc.n_cap))

    def _fallback_full_forward(self, doc: _BatchDoc) -> None:
        """Overflow: discard the unreliable batched slice, recompute from the
        host mirrors, and double the document's row bucket."""
        self.stats.overflows += 1
        self._reingest(doc)
        if doc.row_capacity < doc.n_cap:
            doc.row_capacity = min(doc.row_capacity * 2, doc.n_cap)

    def _grow(self, doc: _BatchDoc) -> None:
        """Slot buffer full: step ``n_cap`` up to the next capacity class
        (slots keep their indices, new free slots appended). With
        ``device_grow`` the resident state is padded ON DEVICE
        (``pad_state``: appended slots are invalid with sentinel positions
        and zero activations, exactly the shape every masked step already
        ignores) — no full forward, and the incremental attention history
        survives, so ``touched_from`` is deliberately NOT cleared. The first
        dispatch in the bigger class re-jits — amortized across the
        fleet."""
        old_cap, new_cap = doc.n_cap, self.padded_cap(doc.n_cap + 1)
        for name, fill in (("tokens", 0), ("valid", False),
                           ("positions", self._pos_sentinel)):
            arr = getattr(doc, name)
            grown = np.full(new_cap, fill, arr.dtype)
            grown[:old_cap] = arr
            setattr(doc, name, grown)
        doc.free.extend(range(new_cap - 1, old_cap - 1, -1))
        doc.n_cap = new_cap
        self.stats.grows += 1
        if self._sugg is not None:  # capacity changed: cache shape unusable
            self._sugg.drop(doc.doc_id)
        if not self.device_grow:
            self._reingest(doc)
            return
        eng = self.engine(self.C, self.R)
        state = self.store.ensure_hot(doc, keep=frozenset((doc.doc_id,)))
        self.store.admit(
            state_nbytes_for(new_cap, eng.L, eng.meta)
            - state_nbytes_for(old_cap, eng.L, eng.meta),
            keep=frozenset((doc.doc_id,)))
        new_state = eng.pad_state(state, new_cap,
                                  pos_fill=self._pos_sentinel)
        self.store.set_hot(doc, new_state)
        self.stats.device_grows += 1
        self.stats.kernel_launches += 1
        self._count_shape(("pad", old_cap, new_cap))

    def _defrag(self, doc: _BatchDoc) -> None:
        """Gap exhaustion: re-spread every position id evenly (paper §3.3,
        "akin to defragmentation"). Every cached activation depends on its
        position embedding, so the full forward is unavoidable — but with
        ``device_defrag`` the slot compaction that precedes it runs ON
        DEVICE (``gather_slots`` permutes the resident buffers into
        sequence order) instead of shipping token mirrors through host
        memory, and the compacted layout feeds the SAME compiled
        ``full_forward`` a re-ingest would run — bitwise-identical output
        by construction (tested against the host re-ingest oracle in
        tests/test_fused_step.py)."""
        self.stats.defrags += 1
        if self._sugg is not None:  # every position id changed: nothing in
            self._sugg.drop(doc.doc_id)  # the doc's decode cache is reusable
        doc.invalid_from = 0
        self._stale(doc)
        if not self.device_defrag:
            doc.allocator.defragment()
            doc.positions[np.asarray(doc.slots, np.int64)] = \
                doc.allocator.snapshot()
            self._reingest(doc)
            return
        eng = self.engine(self.C, self.R)
        state = self.store.ensure_hot(doc, keep=frozenset((doc.doc_id,)))
        n = doc.n
        # compaction permutation: live slots in sequence order first, then
        # the free tail — slot i of the permuted buffers is token i of the
        # document, so the re-spread ids land 1:1
        order = np.concatenate([np.asarray(doc.slots, np.int32),
                                np.asarray(doc.free, np.int32)])
        doc.allocator.defragment()
        respread = doc.allocator.snapshot()
        permuted = eng.gather_slots(state, jnp.asarray(order))
        new_positions = np.full(doc.n_cap, self._pos_sentinel, np.int32)
        new_positions[:n] = respread
        new_valid = np.zeros(doc.n_cap, bool)
        new_valid[:n] = True
        new_state = eng.full_forward(permuted.tokens,
                                     _device_copy(new_positions),
                                     _device_copy(new_valid))
        self.store.set_hot(doc, new_state)
        # host mirrors follow the compaction so slot indices keep matching
        doc.tokens = doc.tokens[order]
        doc.valid = new_valid
        doc.positions = new_positions
        doc.slots = list(range(n))
        doc.free = list(range(doc.n_cap - 1, n - 1, -1))
        doc.touched_from = None
        self.stats.device_defrags += 1
        self.stats.full_forwards += 1
        self.stats.kernel_launches += 2
        self._count_shape(("full", doc.n_cap))

    # ------------------------------------------------------------ suggestions

    def submit_suggest(self, doc_id: str, n_new: int = 8) -> None:
        """Open a standing suggestion subscription: after every scheduling
        round, the document's greedy ``n_new``-token continuation is kept
        fresh (refreshed whenever edits made it stale, reusing every cache
        row before the earliest invalidated position). Cancel with
        ``cancel_suggest``."""
        doc = self.docs[doc_id]
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        if doc.suggest_n != n_new:
            doc.suggest_n = int(n_new)
            doc.suggest_fresh = False

    def cancel_suggest(self, doc_id: str) -> None:
        doc = self.docs[doc_id]
        doc.suggest_n = 0
        doc.suggestion = None
        doc.suggest_fresh = False

    def suggestion(self, doc_id: str) -> Optional[np.ndarray]:
        """The last refreshed continuation, or None while it is stale
        (a newer edit arrived and the next round has not served it yet)."""
        doc = self.docs[doc_id]
        return doc.suggestion.copy() if doc.suggest_fresh else None

    def suggest(self, doc_id: str, n_new: int = 8) -> np.ndarray:
        """Flush the document's pending edits and return a fresh greedy
        continuation (subscribing the document if it was not already).

        Redundant-refresh fast path: when nothing changed since the last
        refresh (no pending edits, ``invalid_from`` watermark clear) and the
        cached continuation covers ``n_new``, the cached tokens are returned
        WITHOUT re-entering the prefill/dispatch path — greedy decoding is
        deterministic, so an unchanged document has an unchanged
        continuation (regression-tested by
        tests/test_async_server.py::test_back_to_back_suggest_no_redispatch).
        """
        if n_new < 1:
            raise ValueError("n_new must be >= 1")
        doc = self.docs[doc_id]
        if (not doc.pending and doc.suggest_fresh and doc.invalid_from is None
                and doc.suggestion is not None
                and len(doc.suggestion) >= n_new):
            self.stats.suggest_cached_hits += 1
            return doc.suggestion[:n_new].copy()
        self.submit_suggest(doc_id, n_new)
        self.flush()
        if not doc.suggest_fresh:
            self._refresh_doc(doc)
        return doc.suggestion.copy()

    def _refresh_suggestions(self) -> None:
        """Serve stale suggestion subscriptions, grouped by capacity bucket
        (the same grouping the edit dispatcher uses, so refreshes ride the
        scheduling round). A document with queued edits stays stale — its
        pending suggestion was invalidated by the newer edits and refreshes
        only after they apply."""
        ready = [d for d in self.docs.values()
                 if d.suggest_n > 0 and not d.suggest_fresh and not d.pending]
        for doc in sorted(ready, key=lambda d: (d.n_cap, d.doc_id)):
            self._refresh_doc(doc)

    def _refresh_doc(self, doc: _BatchDoc) -> None:
        # Redundant-refresh fast path: the document's content watermarks are
        # unchanged since the suggestion it already holds (``invalid_from``
        # clear), so the deterministic greedy continuation cannot differ —
        # serve the cached tokens without any prefill/dispatch. Reached e.g.
        # by a re-subscription at an unchanged-or-shorter length.
        if (doc.invalid_from is None and doc.suggestion is not None
                and len(doc.suggestion) >= doc.suggest_n):
            doc.suggestion = doc.suggestion[:doc.suggest_n]
            doc.suggest_fresh = True
            self.stats.suggest_cached_hits += 1
            return
        sugg = self.suggester
        eng = self.engine(self.C, self.R)
        self.store.ensure_hot(doc)  # KV export reads the device state
        on_token = None
        if self.on_suggest_token is not None:
            serial, hook = doc.suggest_serial + 1, self.on_suggest_token

            def on_token(tok, _id=doc.doc_id, _serial=serial, _hook=hook):
                _hook(_id, _serial, int(np.asarray(tok).reshape(-1)[0]))
        try:
            toks = sugg.refresh(
                eng, doc.state, key=doc.doc_id, n_new=doc.suggest_n,
                invalid_from=doc.invalid_from,
                export_invalid_from=doc.touched_from, on_token=on_token)
        except PositionHeadroomError:
            # the tail gap is exhausted: re-spread the ids (a scheduled
            # defrag + full-forward re-ingest) and retry once
            self._defrag(doc)
            toks = sugg.refresh(
                eng, doc.state, key=doc.doc_id, n_new=doc.suggest_n,
                invalid_from=doc.invalid_from,
                export_invalid_from=doc.touched_from, on_token=on_token)
        doc.suggestion = toks
        doc.suggest_fresh = True
        doc.invalid_from = None
        doc.suggest_serial += 1
        self.stats.suggest_refreshes += 1

    # ------------------------------------------------------------- outputs

    def _flushed(self, doc_id: str) -> _BatchDoc:
        doc = self.docs[doc_id]
        if doc.pending:
            raise RuntimeError(
                f"document {doc_id!r} has {len(doc.pending)} unflushed edits")
        return doc

    def tokens(self, doc_id: str) -> np.ndarray:
        """The document's tokens in sequence order."""
        return self._flushed(doc_id).seq_tokens().copy()

    def state(self, doc_id: str) -> JitState:
        doc = self._flushed(doc_id)
        return self.store.ensure_hot(doc)

    def logits(self, doc_id: str) -> np.ndarray:
        doc = self._flushed(doc_id)
        eng = self.engine(self.C, self.R)
        state = self.store.ensure_hot(doc)
        return np.asarray(eng.logits_at(state, jnp.int32(doc.slots[-1])))

    # -------------------------------------------------- migration (DESIGN.md §11)

    def checkpoint_document(self, doc_id: str, path: str) -> None:
        """Write a flushed document's FULL serving snapshot to ``path``
        (atomic) while keeping it open: the JitState, the allocator ids, the
        host mirrors and — critically — the slot layout and free-list order.
        Attention reduces over the slot axis, so bit-exact adoption must
        reproduce the layout verbatim; ``import_document`` does. The
        document is rehydrated first, so a warm/cold resident checkpoints
        the same bits a hot one would."""
        doc = self._flushed(doc_id)
        # ensure_hot FIRST: it releases any cold holding (which may live at
        # this very path when the store shares the fleet's cold directory) —
        # writing before rehydrating would let the release delete the export
        state = self.store.ensure_hot(doc)
        save_serving_document(
            path, state_to_host(state),
            allocator_ids=doc.allocator.snapshot(),
            mirrors={
                "tokens": doc.tokens.copy(),
                "valid": doc.valid.copy(),
                "positions": doc.positions.copy(),
                "slots": np.asarray(doc.slots, np.int32),
                "free": np.asarray(doc.free, np.int32),
            },
            meta={
                "doc_id": doc_id,
                "row_capacity": int(doc.row_capacity),
                "n_virtual": int(doc.n_virtual),
                "suggest_n": int(doc.suggest_n),
                "pos_pool": int(self.pos_pool),
                "invalid_from": doc.invalid_from,
                "touched_from": doc.touched_from,
                "consistent": True,  # flushed + out-of-round by construction
            })

    def export_document(self, doc_id: str, path: str) -> None:
        """Hand a document off for migration: checkpoint, then close. The
        snapshot at ``path`` survives the close (checkpoints are ordinary
        files, not store-held cold spills) and a peer ``import_document``
        resumes the document bit-exactly (DESIGN.md §11)."""
        self.checkpoint_document(doc_id, path)
        self.close_document(doc_id)
        self.stats.exports += 1

    def import_document(self, doc_id: str, path: str, *,
                        remove: bool = True) -> None:
        """Adopt a document from a serving snapshot — the receiving half of
        migration and failover. A pure re-upload, never a recompute: the
        slot buffer, free-list order, allocator ids and device state are
        restored verbatim, so every subsequent dispatch, logits read and
        suggestion refresh is bitwise-identical to a server that never
        migrated the document (tests/test_fleet.py). Snapshots flagged
        ``consistent: False`` (captured mid-round by an eviction) are
        refused — their mirrors run ahead of their state."""
        if doc_id in self.docs:
            raise KeyError(f"document {doc_id!r} already open")
        state_h, ids, mirrors, meta = restore_serving_document(path)
        if not meta.get("consistent", True):
            raise ValueError(
                f"snapshot for {doc_id!r} is marked inconsistent (captured "
                "mid-round); re-open the document from its tokens instead")
        if meta.get("doc_id") not in (None, doc_id):
            raise ValueError(
                f"snapshot at {path} belongs to {meta['doc_id']!r}, "
                f"not {doc_id!r}")
        pool = meta.get("pos_pool")
        if pool is not None and int(pool) != self.pos_pool:
            raise ValueError(
                f"snapshot position pool {pool} != server pool "
                f"{self.pos_pool} — position ids would not be comparable")
        tokens = np.array(mirrors["tokens"], np.int32, copy=True)
        n_cap = int(tokens.shape[0])
        eng = self.engine(self.C, self.R)
        self.store.admit(state_nbytes_for(n_cap, eng.L, eng.meta))
        alloc = PositionAllocator(1, self.pos_pool)
        alloc.restore([int(i) for i in np.asarray(ids)])
        doc = _BatchDoc(
            doc_id=doc_id, tokens=tokens,
            valid=np.array(mirrors["valid"], bool, copy=True),
            positions=np.array(mirrors["positions"], np.int32, copy=True),
            slots=[int(s) for s in mirrors["slots"]],
            free=[int(s) for s in mirrors["free"]],
            n_cap=n_cap, row_capacity=int(meta["row_capacity"]),
            allocator=alloc, state=state_from_host(state_h),
            n_virtual=int(meta.get("n_virtual", len(mirrors["slots"]))),
            suggest_n=int(meta.get("suggest_n", 0)),
            invalid_from=meta.get("invalid_from"),
            touched_from=meta.get("touched_from"))
        self.docs[doc_id] = doc
        self.store.register(doc)
        self.stats.docs += 1
        self.stats.imports += 1
        if remove:
            os.remove(path)
