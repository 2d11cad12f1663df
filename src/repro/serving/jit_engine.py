"""TPU-native incremental inference: the static-shape, jit-able version of
``repro.core.incremental`` (DESIGN.md §3 "dirty-slot buffers").

The host-side NumPy engine uses dynamic dirty sets and dynamically grows /
shrinks its arrays on insert and delete — ideal for op counting, impossible
to jit. This module implements the same algorithm for the FULL edit algebra
(replace / insert / delete) with **static capacities** over a **slot-buffer
document layout**:

* ``n_cap`` — slot capacity: every document lives in a fixed-size buffer of
  ``n_cap`` slots with a ``valid`` mask and an ``n_real`` count. Sequence
  order is derived from the *gapped position ids* (paper §3.3), never from
  the array index: slot j precedes slot i iff ``positions[j] <= positions[i]``
  and both are valid. Inserting a token claims any free slot and a mid-gap
  position id; deleting invalidates a slot in place. No data moves.
* ``C`` — edit capacity: how many slots change per step (the edit bucket);
* ``R`` — propagation capacity: how many rows may change per layer.

Every step is one fixed-shape computation: gather dirty rows → dense
per-location ops → column patch over all rows (the ``incr_patch`` Pallas
kernel's math, ΔT with the old contribution subtracted and the new one
added) → re-quantize (the ``vq_assign`` trick in score space) → scatter
updates. Inserts add a column whose *old* contribution is exactly zero
(the claimed slot's ``k``/``vc`` are zeroed first; ``gelu(0)·0 = 0``),
deletes subtract their column via the same ΔT patch with the *new*
contribution zeroed — so all three ops share one compiled step. The count
renormalization that inserts/deletes imply is automatic: counts are
recomputed from the valid mask and position order each step. If more than
``R`` rows change at any layer, the step reports ``overflow=True`` and the
caller re-runs a full forward (the capacity-doubling / re-jit policy of
serving systems).

State layout (per document, all jnp, layer-stacked where possible):
  tokens:    [n_cap]  int32  (free slots hold garbage)
  positions: [n_cap]  int32  gapped ids; unique among valid slots
  valid:     [n_cap]  bool
  n_real:    []       int32  == valid.sum()
  x:      [L+1, n_cap, d]   residual stream snapshots
  q/k/v:  [L, n_cap, H, dh]
  vc:     [L, n_cap, H, Q]  per-head value·codebook products
  T:      [L, n_cap, H, Q]  accumulated scores
  codes:  [L, n_cap, hq]

Free/invalid slots carry garbage activations; every mask (causal, counts,
changed-row detection) ANDs with ``valid`` so garbage never reaches a valid
row. Exactness: identical codes / float-tolerance states vs the NumPy
engine over mixed edit streams (tests/test_jit_engine.py,
tests/test_mixed_edit_streams.py).

On top of the VQ code-match gate sits an optional **sigma-delta tier**
(``delta_threshold``, DESIGN.md §10): a code-flipped row propagates
downstream only when its recomputed hidden state drifts more than the
threshold (L∞) from the value it last transmitted. ``delta_threshold=0.0``
is bit-identical to the ungated engine by construction
(tests/test_delta_threshold.py); > 0 trades bounded activation drift for
fewer propagated rows — the tolerance knob between bit-exact serving and
aggressive reuse.

Batched serving
---------------
Because every step is a fixed-shape pure function of ``(JitState, edit
bucket)``, a fleet of documents that share the same capacities
``(n_cap, C, R)`` can be served as ONE vmapped step: stack their states
along a leading batch axis and vmap ``_full_forward_impl`` /
``_apply_edits_impl`` (``repro.serving.batch_engine.BatchedJitEngine``).
Overflow is reported per-document — the scheduler
(``repro.serving.batch_server.BatchServer``) re-runs only the overflowed
documents with a full forward and doubles their row capacity ``R`` (a
re-jit, amortized over the fleet). The un-jitted ``*_impl`` methods exist
precisely so the batched engine can wrap them in ``jit(vmap(...))``
without nesting jit caches.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.common.precision import pinned_precision

# Edit opcodes for the generic ``apply_edits`` step (int32 bucket entries).
OP_REPLACE = 0
OP_INSERT = 1
OP_DELETE = 2


class JitState(NamedTuple):
    tokens: jax.Array  # [n_cap] int32
    positions: jax.Array  # [n_cap] int32 (gapped ids; order == sequence order)
    valid: jax.Array  # [n_cap] bool
    n_real: jax.Array  # [] int32
    x: jax.Array  # [L+1, n_cap, d]
    q: jax.Array  # [L, n_cap, H, dh]
    k: jax.Array
    v: jax.Array
    vc: jax.Array  # [L, n_cap, H, Q]
    T: jax.Array  # [L, n_cap, H, Q]
    codes: jax.Array  # [L, n_cap, hq]


class KVExport(NamedTuple):
    """Position-ordered view of a slot buffer's cached keys/values — the
    bridge from the incremental engine to a standard decode KV cache
    (DESIGN.md §5 "suggestion serving").

    All arrays keep the fixed ``n_cap`` extent (jit-friendly): the first
    ``n_real`` rows are the document's valid slots in sequence (position-id)
    order, the tail rows are invalid slots' garbage — a decode cache built
    from this export masks them with its length counter. Every layer column
    the incremental passes left untouched is bit-exact against the
    document's last full forward; touched columns are float-close (the ΔT
    patch accumulates in a different order), which is why the suggestion
    engine re-prefills from the earliest invalidated position instead of
    trusting them bitwise.
    """

    tokens: jax.Array  # [n_cap] int32, sequence-ordered (valid rows first)
    positions: jax.Array  # [n_cap] int32
    order: jax.Array  # [n_cap] int32 — slot index per sequence rank
    k: jax.Array  # [L, n_cap, H, dh] sequence-ordered cached keys
    v: jax.Array  # [L, n_cap, H, dh] sequence-ordered cached values
    n_real: jax.Array  # [] int32 — rows 0..n_real-1 are real


def state_to_host(state: JitState) -> JitState:
    """Snapshot a device-resident ``JitState`` into host-owned numpy arrays.

    The copy is eager (``np.array(..., copy=True)``) so the returned leaves
    share no storage with device buffers — evicting the device state frees
    its memory immediately instead of keeping it alive through a zero-copy
    view (the CPU backend hands out views from ``device_get``). The host
    snapshot is the warm tier of ``repro.serving.state_store`` and the
    payload of its cold (disk) tier; ``state_from_host`` re-uploads it
    bit-exactly."""
    import numpy as np

    return JitState(*(np.array(jax.device_get(leaf), copy=True)
                      for leaf in state))


def state_from_host(host_state: JitState) -> JitState:
    """Re-upload a ``state_to_host`` snapshot. Bit-exact: every leaf is a
    plain dtype round-trip (no recompute), so a rehydrated document is
    indistinguishable from one that was never evicted. The host arrays are
    store-owned and never mutated after the snapshot, so the asynchronous
    device read (see ``batch_server._device_copy``) cannot race anything."""
    return JitState(*(jnp.asarray(leaf) for leaf in host_state))


def state_nbytes(state: JitState) -> int:
    """Exact byte footprint of one document's state (any tier: the device
    layout, the host snapshot and the npz payload all share dtypes)."""
    return sum(leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(state))


def state_nbytes_for(n_cap: int, n_layers: int, meta: dict) -> int:
    """``state_nbytes`` from shapes alone — what a capacity-``n_cap``
    document WILL occupy, before its state exists (the store admits new
    documents and ``n_cap``-doubling re-ingests against this). ``meta`` is
    the engine's weight metadata (``JitIncrementalEngine.meta``). Must match
    ``state_nbytes`` of a real state leaf-for-leaf
    (tests/test_state_store.py::test_state_nbytes_formula_matches)."""
    L, d, H, dh, Q, hq = (n_layers, meta["d"], meta["H"], meta["dh"],
                          meta["Q"], meta["hq"])
    f32 = 4
    return (
        n_cap * 4            # tokens int32
        + n_cap * 4          # positions int32
        + n_cap * 1          # valid bool
        + 4                  # n_real int32
        + (L + 1) * n_cap * d * f32          # x
        + 3 * L * n_cap * H * dh * f32       # q, k, v
        + 2 * L * n_cap * H * Q * f32        # vc, T
        + L * n_cap * hq * 4                 # codes int32
    )


def state_nbytes_for_config(cfg: ArchConfig, n_cap: int) -> int:
    """``state_nbytes_for`` straight from an ``ArchConfig`` — for sizing a
    device budget BEFORE any engine (and its weight flattening) exists,
    e.g. ``BatchServer(device_budget_bytes=k * state_nbytes_for_config(...))``.
    Uses the same field mapping as ``core.incremental.IncrementalEngine``."""
    if cfg.vqt is None:
        raise ValueError("state sizing requires a VQT config")
    meta = dict(d=cfg.d_model, H=cfg.n_heads, dh=cfg.resolved_head_dim,
                Q=cfg.vqt.codebook_size, hq=cfg.vqt.n_heads)
    return state_nbytes_for(n_cap, cfg.n_layers, meta)


def _weights_from_params(params: dict, cfg: ArchConfig):
    """Flatten stage params into per-layer stacked arrays (the engine's
    LayerWeights, vectorized over L)."""
    import numpy as np

    from repro.core.incremental import IncrementalEngine

    eng = IncrementalEngine(params, cfg)  # reuse its (validated) extraction
    stack = lambda f: jnp.asarray(np.stack([f(W) for W in eng.layers]))
    W = {
        "ln1_s": stack(lambda w: w.ln1_s), "ln1_b": stack(lambda w: w.ln1_b),
        "wq": stack(lambda w: w.wq), "bq": stack(lambda w: w.bq),
        "wk": stack(lambda w: w.wk), "bk": stack(lambda w: w.bk),
        "wv": stack(lambda w: w.wv), "bv": stack(lambda w: w.bv),
        "bo": stack(lambda w: w.bo),
        "ln2_s": stack(lambda w: w.ln2_s), "ln2_b": stack(lambda w: w.ln2_b),
        "w_up": stack(lambda w: w.w_up), "b_up": stack(lambda w: w.b_up),
        "w_down": stack(lambda w: w.w_down), "b_down": stack(lambda w: w.b_down),
        "cb_per_head": stack(
            lambda w: w.codebook.reshape(eng.hq, eng.Q, eng.heads_per_vq, eng.dh)
            .transpose(0, 2, 1, 3).reshape(eng.H, eng.Q, eng.dh)
        ),
        "vq_bias": stack(lambda w: w.vq_bias),
        "c_wo": stack(lambda w: w.c_wo),
    }
    meta = dict(H=eng.H, dh=eng.dh, d=eng.d, hq=eng.hq, Q=eng.Q,
                heads_per_vq=eng.heads_per_vq, scale=float(eng.scale))
    extras = {
        "tok_emb": jnp.asarray(eng.tok_emb), "pos_emb": jnp.asarray(eng.pos_emb),
        "fn_s": jnp.asarray(eng.fn_s), "fn_b": jnp.asarray(eng.fn_b),
        "head_w": jnp.asarray(eng.head_w),
    }
    return W, extras, meta


def jit_with_weights(fn):
    """``jax.jit`` an engine method ``fn(self, wts, *args)`` with the engine
    static and its device weights passed as a jit ARGUMENT, never closed
    over: a closed-over array lowers to an HLO constant, so every compiled
    shape would carry its own copy of every weight. Its matmuls trace at
    the serving precision (``repro.common.precision``). The returned method
    takes ``(self, *args)`` and supplies ``self.wts``; the jitted function
    itself stays reachable as ``.jitted`` (``.jitted.lower(eng, wts, ...)``
    compiles a step from shapes alone)."""
    jitted = jax.jit(pinned_precision(fn), static_argnums=0)

    @functools.wraps(fn)
    def call(self, *args, **kwargs):
        return jitted(self, self.wts, *args, **kwargs)

    call.jitted = jitted
    return call


def _ln(x, s, b, eps=1e-5):
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * s + b


def _gelu(x):
    return jax.nn.gelu(x.astype(jnp.float32), approximate=True)


def _order_masks(positions: jax.Array, valid: jax.Array):
    """Causal structure of a slot buffer, derived from position-id order.

    causal[i, j] = valid[j] & (positions[j] <= positions[i]) — slot j is an
    attended (past-or-self) column of slot i. Position ids are unique among
    valid slots (the allocator's invariant), so <= is a strict order plus
    self. counts[i] = number of columns row i attends (clamped to 1 so
    invalid rows' garbage normalization never divides by zero).
    """
    causal = ((positions[None, :] <= positions[:, None])
              & valid[None, :]).astype(jnp.float32)  # [n, n] rows=i, cols=j
    counts = jnp.maximum(causal.sum(-1), 1.0)  # [n]
    return causal, counts


class JitIncrementalEngine:
    """Static-capacity incremental engine for the full VQT edit algebra."""

    def __init__(self, params: dict, cfg: ArchConfig, *, edit_capacity: int = 8,
                 row_capacity: int = 64, use_patch_kernel: bool = False,
                 use_fused_kernel: bool = False, delta_threshold: float = 0.0,
                 _weights=None):
        self.cfg = cfg
        self.C = edit_capacity
        self.R = row_capacity
        # Route the column patch through the incr_patch Pallas kernel instead
        # of the inline einsum (same math; the kernel adds a batch grid
        # dimension under vmap — see batch_engine.py).
        self.use_patch_kernel = use_patch_kernel
        # Fuse column patch + T accumulate + requantize into ONE Pallas
        # launch per layer (kernels/fused_step, DESIGN.md §9). Wins over
        # use_patch_kernel, which it subsumes.
        self.use_fused_kernel = use_fused_kernel
        # Sigma-delta propagation gate (DESIGN.md §10): a VQ-code-flipped
        # row propagates downstream only when its recomputed next-layer
        # value drifts more than this (L∞) from the value it last
        # transmitted. 0.0 (the default) traces the EXACT pre-threshold
        # jaxpr — bit-identical serving — because the gate is guarded at
        # the Python level, never by a traced compare. The engine is a jit
        # static arg, so the Python float is a compile-time constant.
        if delta_threshold < 0.0:
            raise ValueError("delta_threshold must be >= 0")
        self.delta_threshold = float(delta_threshold)
        if _weights is not None:
            W, extras, self.meta = _weights
        else:
            W, extras, self.meta = _weights_from_params(params, cfg)
        # the device-resident weight pytree every jitted entry point takes
        # as an argument (``jit_with_weights``)
        self.wts = {"W": W, "extras": extras}
        self.L = W["wq"].shape[0]

    @property
    def weights(self):
        """(W, extras, meta) — pass as ``_weights=`` to share the extracted
        parameter stacks between sibling engines (e.g. per-capacity-bucket
        re-jits in the batch server)."""
        return self.wts["W"], self.wts["extras"], self.meta

    # ------------------------------------------------------------ full pass

    @jit_with_weights
    def full_forward(self, wts, tokens: jax.Array, positions: jax.Array,
                     valid: Optional[jax.Array] = None) -> JitState:
        """Ingest a slot buffer. ``valid=None`` means every slot is real (the
        plain fixed-length document of the replace-only path)."""
        return self._full_forward_impl(wts, tokens, positions, valid)

    def _full_forward_impl(self, wts, tokens: jax.Array, positions: jax.Array,
                           valid: Optional[jax.Array] = None) -> JitState:
        m = self.meta
        n = tokens.shape[0]
        if valid is None:
            valid = jnp.ones((n,), bool)
        valid = valid.astype(bool)
        x0 = wts["extras"]["tok_emb"][tokens] + wts["extras"]["pos_emb"][positions]
        causal, counts = _order_masks(positions, valid)

        def layer(x, Wl):
            h = _ln(x, Wl["ln1_s"], Wl["ln1_b"])
            q = jnp.einsum("nd,dhe->nhe", h, Wl["wq"]) + Wl["bq"]
            k = jnp.einsum("nd,dhe->nhe", h, Wl["wk"]) + Wl["bk"]
            v = jnp.einsum("nd,dhe->nhe", h, Wl["wv"]) + Wl["bv"]
            vc = jnp.einsum("nhe,hqe->nhq", v, Wl["cb_per_head"])
            w = _gelu(jnp.einsum("nhe,jhe->hnj", q, k) * m["scale"]) * causal[None]
            T = jnp.einsum("hnj,jhq->nhq", w, vc)
            s = T.reshape(n, m["hq"], m["heads_per_vq"], m["Q"]).sum(2)
            s = s / counts[:, None, None] + Wl["vq_bias"][None]
            codes = jnp.argmax(s, axis=-1).astype(jnp.int32)
            attn = Wl["bo"][None] + sum(
                Wl["c_wo"][hh][codes[:, hh]] for hh in range(m["hq"])
            )
            x_mid = x + attn
            h2 = _ln(x_mid, Wl["ln2_s"], Wl["ln2_b"])
            ffn = _gelu(h2 @ Wl["w_up"] + Wl["b_up"]) @ Wl["w_down"] + Wl["b_down"]
            return x_mid + ffn, (q, k, v, vc, T, codes)

        xs = [x0]
        qs, ks, vs, vcs, Ts, cds = [], [], [], [], [], []
        x = x0
        for li in range(self.L):
            with jax.named_scope(f"layer{li}"):
                Wl = jax.tree.map(lambda a: a[li], wts["W"])
                x, (q, k, v, vc, T, codes) = layer(x, Wl)
            xs.append(x)
            qs.append(q); ks.append(k); vs.append(v)
            vcs.append(vc); Ts.append(T); cds.append(codes)
        st = lambda l: jnp.stack(l)
        return JitState(tokens.astype(jnp.int32), positions.astype(jnp.int32),
                        valid, valid.sum(dtype=jnp.int32),
                        st(xs), st(qs), st(ks), st(vs), st(vcs), st(Ts), st(cds))

    # ------------------------------------------------------------ edit step

    @jit_with_weights
    def apply_edits(self, wts, state: JitState, slot: jax.Array, tok: jax.Array,
                    pos_id: jax.Array, op: jax.Array
                    ) -> tuple[JitState, jax.Array]:
        """The generic fixed-shape edit step: up to ``C`` typed edits at once.

        slot:   [C] int32 — target slot (pad unused entries with -1);
        tok:    [C] int32 — new token (replace/insert; ignored for delete);
        pos_id: [C] int32 — fresh gapped position id (insert only);
        op:     [C] int32 — OP_REPLACE / OP_INSERT / OP_DELETE.

        Bucket invariants (the scheduler's job): slots are distinct within a
        bucket; an insert targets a *free* slot with a position id strictly
        between its sequence neighbours'; replace/delete target valid slots.
        Returns (new_state, overflow) — overflow=True means the propagation
        bucket R was exceeded at some layer and the result is UNRELIABLE
        (caller must full_forward). Overflow is detected on the PRE-gate
        changed set, so a ``delta_threshold`` never masks an overflow —
        thresholding only ever makes the flag conservative."""
        return self._apply_edits_impl(wts, state, slot, tok, pos_id, op)

    @jit_with_weights
    def apply_replaces(self, wts, state: JitState, edit_pos: jax.Array,
                       edit_tok: jax.Array) -> tuple[JitState, jax.Array]:
        """Replace-only bucket (back-compat surface). edit_pos: [C] int32
        slot indices (pad with -1); edit_tok: [C] int32."""
        z = jnp.zeros_like(edit_pos)
        return self._apply_edits_impl(wts, state, edit_pos, edit_tok, z, z)

    @jit_with_weights
    def apply_inserts(self, wts, state: JitState, slot: jax.Array, tok: jax.Array,
                      pos_id: jax.Array) -> tuple[JitState, jax.Array]:
        """Insert-only bucket: claim free slots ``slot`` (pad with -1), give
        them tokens ``tok`` and fresh mid-gap position ids ``pos_id``."""
        op = jnp.where(slot >= 0, OP_INSERT, 0).astype(jnp.int32)
        return self._apply_edits_impl(wts, state, slot, tok, pos_id, op)

    @jit_with_weights
    def apply_deletes(self, wts, state: JitState,
                      slot: jax.Array) -> tuple[JitState, jax.Array]:
        """Delete-only bucket: invalidate slots ``slot`` (pad with -1) and
        subtract their column contributions."""
        z = jnp.zeros_like(slot)
        op = jnp.where(slot >= 0, OP_DELETE, 0).astype(jnp.int32)
        return self._apply_edits_impl(wts, state, slot, z, z, op)

    def _apply_edits_impl(self, wts, state: JitState, slot: jax.Array,
                          tok: jax.Array, pos_id: jax.Array, op: jax.Array
                          ) -> tuple[JitState, jax.Array]:
        m = self.meta
        R = self.R
        n = state.tokens.shape[0]
        valid_e = slot >= 0
        slot_safe = jnp.where(valid_e, slot, 0)
        opv = jnp.where(valid_e, op, -1)
        is_ins = opv == OP_INSERT
        is_del = opv == OP_DELETE
        has_new = valid_e & ~is_del  # slot holds a (new) token afterwards
        had_old = valid_e & ~is_ins  # slot contributed a column before

        # -------- slot metadata: tokens / positions / valid / n_real
        # Masked bucket entries scatter to index n — out of bounds, so
        # mode="drop" discards them (NOT -1, which jnp wraps to the last
        # slot) — no read-modify-write dance, no duplicate-index hazards.
        drop = jnp.int32(n)
        tokens = state.tokens.at[jnp.where(has_new, slot, drop)].set(
            tok, mode="drop")
        positions = state.positions.at[jnp.where(is_ins, slot, drop)].set(
            pos_id, mode="drop")
        # Deleted slots keep their position id: the ΔT patch below still
        # needs it to address the rows that used to attend the column.
        valid = state.valid.at[jnp.where(is_ins, slot, drop)].set(
            True, mode="drop")
        valid = valid.at[jnp.where(is_del, slot, drop)].set(False, mode="drop")
        n_real = (state.n_real + is_ins.sum(dtype=jnp.int32)
                  - is_del.sum(dtype=jnp.int32))

        with jax.named_scope("masks"):
            causal, counts = _order_masks(positions, valid)

        # Inserted slots may hold a stale tenant's activations. Zero their
        # k/vc across all layers so the "old contribution" the ΔT patch
        # subtracts is exactly zero (gelu(0)·0 = 0) — the slot-buffer
        # analogue of the NumPy engine inserting a zero row.
        ins_slot = jnp.where(is_ins, slot, drop)
        k_base = state.k.at[:, ins_slot].set(0.0, mode="drop")
        vc_base = state.vc.at[:, ins_slot].set(0.0, mode="drop")

        # layer-0 dirty bucket = the edit bucket
        x_rows = (wts["extras"]["tok_emb"][tokens[slot_safe]]
                  + wts["extras"]["pos_emb"][positions[slot_safe]])
        new_x = [state.x[0].at[jnp.where(has_new, slot, drop)].set(
            x_rows, mode="drop")]
        # rows to recompute this layer (gathered indices + occupancy mask)
        dirty_idx = slot_safe  # [C]
        new_mask = has_new
        # columns to patch this layer. A deleted slot contributes an
        # old-only column at EVERY layer (its cached k/vc still sit in every
        # layer's T sums), but it is never a recomputed row — so the column
        # set is the row set at layer 0 and row-set ∪ delete-slots below.
        col_idx = slot_safe
        col_old = had_old  # subtract the old contribution of these columns
        col_new = has_new  # add the new contribution of these columns

        new_q, new_k, new_v, new_vc, new_T, new_codes = [], [], [], [], [], []
        overflow = jnp.asarray(False)

        for li in range(self.L):
            with jax.named_scope(f"layer{li}"):
                Wl = jax.tree.map(lambda a: a[li], wts["W"])
                x_in = new_x[li]
                with jax.named_scope("qkv"):
                    # per-location at dirty rows (garbage lanes are masked
                    # out below)
                    h = _ln(x_in[dirty_idx], Wl["ln1_s"], Wl["ln1_b"])
                    q_n = jnp.einsum("cd,dhe->che", h, Wl["wq"]) + Wl["bq"]
                    k_n = jnp.einsum("cd,dhe->che", h, Wl["wk"]) + Wl["bk"]
                    v_n = jnp.einsum("cd,dhe->che", h, Wl["wv"]) + Wl["bv"]
                    vc_n = jnp.einsum("che,hqe->chq", v_n, Wl["cb_per_head"])

                    upd = jnp.where(new_mask, dirty_idx, drop)
                    q_all = state.q[li].at[upd].set(q_n, mode="drop")
                    k_all = k_base[li].at[upd].set(k_n, mode="drop")
                    v_all = state.v[li].at[upd].set(v_n, mode="drop")
                    vc_all = vc_base[li].at[upd].set(vc_n, mode="drop")
                    k_old = k_base[li][col_idx]
                    vc_old = vc_base[li][col_idx] * col_old[:, None, None]
                    k_new = k_all[col_idx]
                    vc_new = vc_all[col_idx] * col_new[:, None, None]

                with jax.named_scope("masks"):
                    # column patch over ALL rows: ΔT = new − old
                    # contributions. Column order comes from position ids;
                    # rows are masked by the valid mask so free slots never
                    # accumulate patches.
                    col_mask = (
                        (col_old | col_new)[None, :]
                        & (positions[col_idx][None, :] <= positions[:, None])
                    ).astype(jnp.float32)  # [n, Cd]
                    row_valid = valid.astype(jnp.float32)
                    causal_rows = causal[dirty_idx]  # [Cd, n]

                with jax.named_scope("patch"):
                    # dirty rows: full row recompute (their causal row of
                    # the position-order mask already reflects
                    # inserts/deletes). Hoisted before the patch so the
                    # fused path can pre-scatter it and exclude those rows
                    # from the patch mask — per row the result is identical
                    # to patch-then-overwrite (a dirty row's patch was
                    # discarded by the overwrite; a clean row's patch is
                    # unchanged).
                    w_rows = _gelu(
                        jnp.einsum("che,jhe->hcj", q_all[dirty_idx], k_all)
                        * m["scale"]) * causal_rows[None]
                    T_rows = jnp.einsum("hcj,jhq->chq", w_rows, vc_all)
                    if self.use_fused_kernel:
                        from repro.kernels.fused_step import (
                            fused_patch_assign,
                        )

                        # patch + T accumulate + requantize in ONE launch:
                        # the mask folds every gate (live columns, causal
                        # order, row validity, dirty-row exclusion), so the
                        # compiled shape is blind to which rows/columns are
                        # live — the ragged capacity-class contract
                        # (DESIGN.md §9)
                        dirty_dense = jnp.zeros((n,), jnp.float32).at[
                            upd].set(1.0, mode="drop")
                        pmask = col_mask * (row_valid
                                            * (1.0 - dirty_dense))[:, None]
                        T_base = state.T[li].at[upd].set(T_rows, mode="drop")
                        T_all, codes = fused_patch_assign(
                            state.q[li],
                            k_new.transpose(1, 0, 2),
                            k_old.transpose(1, 0, 2),
                            vc_new.transpose(1, 0, 2),
                            vc_old.transpose(1, 0, 2),
                            pmask, T_base, counts, Wl["vq_bias"],
                            heads_per_vq=m["heads_per_vq"],
                        )
                    else:
                        if self.use_patch_kernel:
                            from repro.kernels.incr_patch import incr_patch

                            dT = incr_patch(
                                state.q[li],
                                k_new.transpose(1, 0, 2),
                                k_old.transpose(1, 0, 2),
                                vc_new.transpose(1, 0, 2),
                                vc_old.transpose(1, 0, 2),
                                col_mask,
                                row_valid=row_valid,
                            )
                        else:
                            cm = col_mask * row_valid[:, None]
                            s_new = jnp.einsum("nhe,che->nhc", state.q[li],
                                               k_new) * m["scale"]
                            s_old = jnp.einsum("nhe,che->nhc", state.q[li],
                                               k_old) * m["scale"]
                            dT = jnp.einsum(
                                "nhc,chq->nhq", _gelu(s_new) * cm[:, None, :],
                                vc_new) - jnp.einsum(
                                "nhc,chq->nhq", _gelu(s_old) * cm[:, None, :],
                                vc_old)
                        T_all = state.T[li] + dT
                        T_all = T_all.at[upd].set(T_rows, mode="drop")

                with jax.named_scope("requant"):
                    if not self.use_fused_kernel:
                        # re-quantize all rows (cheap: O(n·Q)); counts
                        # renormalization after inserts/deletes is
                        # automatic — counts came from the mask. (The
                        # fused kernel requantizes inside its patch.)
                        s = T_all.reshape(n, m["hq"], m["heads_per_vq"],
                                          m["Q"]).sum(2)
                        s = s / counts[:, None, None] + Wl["vq_bias"][None]
                        codes = jnp.argmax(s, axis=-1).astype(jnp.int32)
                    changed = jnp.any(codes != state.codes[li],
                                      axis=-1) & valid
                    changed = changed.at[upd].set(True, mode="drop")
                    n_changed = changed.sum()
                    overflow = overflow | (n_changed > R)

                with jax.named_scope("propagate"):
                    # gather up to R changed rows into the next dirty bucket
                    scores = jnp.where(changed, 1.0, 0.0)
                    _, next_idx = jax.lax.top_k(scores, min(R, n))
                    next_valid = changed[next_idx]

                with jax.named_scope("mlp"):
                    attn = Wl["bo"][None] + sum(
                        Wl["c_wo"][hh][codes[next_idx][:, hh]]
                        for hh in range(m["hq"]))
                    x_mid = x_in[next_idx] + attn
                    h2 = _ln(x_mid, Wl["ln2_s"], Wl["ln2_b"])
                    ffn = (_gelu(h2 @ Wl["w_up"] + Wl["b_up"]) @ Wl["w_down"]
                           + Wl["b_down"])
                    x_out_rows = x_mid + ffn

                    keep = next_valid
                    if self.delta_threshold > 0.0:
                        # Sigma-delta gate (DESIGN.md §10): compare each
                        # selected row's fresh recompute against the value
                        # it LAST TRANSMITTED — the stored x[li+1] row — so
                        # sub-threshold drift accumulates across steps and
                        # is re-examined on every later code flip.
                        # Suppressed rows still take their new T/codes at
                        # THIS layer (the quantizer state advances; only the
                        # transmission is withheld), write nothing to
                        # x[li+1], and are excluded from the next layer's
                        # dirty bucket and patch columns — i.e. the keep
                        # bits fold into the next layer's engine-built mask.
                        # The Python-level guard keeps the threshold-0 jaxpr
                        # untouched.
                        x_prev_rows = state.x[li + 1][next_idx]
                        if self.use_fused_kernel:
                            from repro.kernels.fused_step import delta_gate

                            moved = delta_gate(x_out_rows, x_prev_rows,
                                               self.delta_threshold)
                        else:
                            moved = (jnp.max(jnp.abs(x_out_rows
                                                     - x_prev_rows),
                                             axis=-1) > self.delta_threshold)
                        keep = next_valid & moved

                    x_next = state.x[li + 1].at[jnp.where(keep, next_idx,
                                                           drop)].set(
                        x_out_rows, mode="drop")
                new_x.append(x_next)
                new_q.append(q_all); new_k.append(k_all); new_v.append(v_all)
                new_vc.append(vc_all); new_T.append(T_all)
                new_codes.append(codes)
                dirty_idx = next_idx
                new_mask = keep
                # deeper layers: propagated rows patch old→new; deleted
                # slots keep riding along as old-only columns
                col_idx = jnp.concatenate([next_idx, slot_safe])
                col_old = jnp.concatenate([keep, is_del])
                col_new = jnp.concatenate([keep,
                                           jnp.zeros_like(is_del)])

        st = lambda l: jnp.stack(l)
        return JitState(tokens, positions, valid, n_real, st(new_x), st(new_q),
                        st(new_k), st(new_v), st(new_vc), st(new_T),
                        st(new_codes)), overflow

    # ------------------------------------------------------- state surgery

    @functools.partial(jax.jit, static_argnums=(0, 2, 3))
    def pad_state(self, state: JitState, new_cap: int,
                  pos_fill: int = 0) -> JitState:
        """Grow a document's device buffers to a larger capacity class — the
        device-side replacement for the grow-time host re-ingest.

        Appended slots are free (``valid=False``, position ``pos_fill`` —
        the scheduler's pool sentinel — token 0, zero activations): exactly
        the reserve slots a fresh ingest at the bigger class would carry, so
        the first insert into one takes the ordinary insert-into-free-slot
        path (``apply_edits`` zeroes the claimed slot's k/vc itself).
        Existing slots keep their bits untouched — valid rows stay exactly
        what the incremental history produced, no full forward, no host
        round-trip. O(state bytes) device copy; the first dispatch at the
        new class re-jits (the capacity-class-doubling policy)."""
        n = state.tokens.shape[0]
        if new_cap < n:
            raise ValueError(f"pad_state cannot shrink ({n} -> {new_cap})")
        extra = new_cap - n
        tail = lambda a: [(0, 0)] * (a.ndim - 2)
        pad_slot = lambda a: jnp.pad(a, [(0, 0), (0, extra)] + tail(a))
        return JitState(
            tokens=jnp.pad(state.tokens, (0, extra)),
            positions=jnp.pad(state.positions, (0, extra),
                              constant_values=pos_fill),
            valid=jnp.pad(state.valid, (0, extra)),
            n_real=state.n_real,
            x=pad_slot(state.x), q=pad_slot(state.q), k=pad_slot(state.k),
            v=pad_slot(state.v), vc=pad_slot(state.vc), T=pad_slot(state.T),
            codes=pad_slot(state.codes),
        )

    @functools.partial(jax.jit, static_argnums=0)
    def gather_slots(self, state: JitState, order: jax.Array) -> JitState:
        """Permute the slot axis of every leaf by ``order`` ([n_cap] int32,
        a permutation) — the device-side slot rearrangement primitive
        (defrag compaction: valid slots to the front in sequence order, free
        slots to the tail). One fused gather, no host mirror round-trip.
        ``n_real`` is order-invariant. Position ids still name the OLD
        layout's embeddings, so a defrag follows this with the re-spread +
        ``full_forward`` (see ``BatchServer._defrag``)."""
        return JitState(
            tokens=state.tokens[order],
            positions=state.positions[order],
            valid=state.valid[order],
            n_real=state.n_real,
            x=jnp.take(state.x, order, axis=1),
            q=jnp.take(state.q, order, axis=1),
            k=jnp.take(state.k, order, axis=1),
            v=jnp.take(state.v, order, axis=1),
            vc=jnp.take(state.vc, order, axis=1),
            T=jnp.take(state.T, order, axis=1),
            codes=jnp.take(state.codes, order, axis=1),
        )

    # ------------------------------------------------------------ kv export

    @functools.partial(jax.jit, static_argnums=0)
    def export_kv(self, state: JitState) -> KVExport:
        """Gather the slot buffer's cached k/v into sequence order — the
        ``JitState -> KV cache`` bridge for continuation ("suggestion")
        decoding. One fixed-shape gather; see ``KVExport`` for the
        exactness contract."""
        return self._export_kv_impl(state)

    def _export_kv_impl(self, state: JitState) -> KVExport:
        # Invalid slots sort last: their position ids may hold the pool
        # sentinel (which a valid slot could in principle share), so the
        # sort key is lifted above every real id instead of trusting it.
        big = jnp.iinfo(jnp.int32).max
        order = jnp.argsort(jnp.where(state.valid, state.positions, big))
        return KVExport(
            tokens=state.tokens[order],
            positions=state.positions[order],
            order=order.astype(jnp.int32),
            k=jnp.take(state.k, order, axis=1),
            v=jnp.take(state.v, order, axis=1),
            n_real=state.n_real,
        )

    # ------------------------------------------------------------ outputs

    @jit_with_weights
    def logits_last(self, wts, state: JitState) -> jax.Array:
        return self._logits_at_impl(wts, state, -1)

    @jit_with_weights
    def logits_at(self, wts, state: JitState, index: jax.Array) -> jax.Array:
        """Logits at an arbitrary slot — the batched server pads documents to
        a capacity bucket, so "last token" is the slot holding the
        largest-position valid row (the host scheduler tracks it), not -1."""
        return self._logits_at_impl(wts, state, index)

    def _logits_at_impl(self, wts, state: JitState,
                        index: jax.Array) -> jax.Array:
        ex = wts["extras"]
        h = _ln(state.x[-1][index][None], ex["fn_s"], ex["fn_b"])[0]
        return h @ ex["head_w"]
