"""Batched dirty-slot serving: the vmapped static-capacity jit engine.

``JitIncrementalEngine`` serves ONE document per dispatch. Under real
traffic (the ROADMAP's millions-of-users setting) many documents have
pending edits at once, and each bucketed step is a small fixed-shape
program — exactly the shape regime where batching pays. This module vmaps
the engine's un-jitted ``*_impl`` methods over a leading document axis:

* ``BatchedJitState`` — the same ``JitState`` NamedTuple, every leaf with a
  leading ``[B]`` batch axis (``stack_states`` / ``unstack_state`` convert);
* ``batch_full_forward(tokens [B, n], positions [B, n], valid [B, n])`` —
  one fused program ingests B slot-buffer documents;
* ``batch_apply_edits(state, slot/tok/pos_id/op [B, C])`` — one fused step
  applies up to C typed edits (replace / insert / delete, see the opcodes
  in ``jit_engine``) to EACH of B documents and returns a per-document
  ``overflow [B]`` bool vector. Documents in the batch may have disjoint
  edit buckets (pad unused slots with -1) — including all-empty buckets,
  which leave that document unchanged. The op vector is *data*, so
  replace-, insert- and delete-typed scheduler buckets all share this one
  compiled step — no per-op re-jit;
* ``batch_apply_replaces`` / ``batch_apply_inserts`` / ``batch_apply_deletes``
  — typed conveniences over the same impl.

All documents in a batch must share the capacities ``(n_cap, C, R)`` — the
batch server's capacity buckets guarantee this. With
``use_patch_kernel=True`` the per-layer column patch runs through the
``incr_patch`` Pallas kernel; under vmap its grid gains a leading batch
dimension (one ``(doc, row-block, head)`` cell per grid point), so the
batched step reuses the same kernel as single-document serving.

Multi-device serving (DESIGN.md §6)
-----------------------------------
Pass ``mesh=`` (see ``repro.launch.mesh.make_serving_mesh``) to shard the
document axis over a 1-D device mesh: every batched entry point becomes a
``shard_map`` over per-shard ``[B/n_dev, ...]`` slices (the weight
pytree is an argument, replicated on every device), so each device runs
the ordinary vmapped step — including the batched Pallas kernels, whose
grids see only the local batch slice —
and no cross-device communication exists anywhere in a dispatch (sequence
order is position-id order *within* each document, so the batch axis is
embarrassingly parallel). ``B`` must be a multiple of the mesh's batch
axis; the batch server pads dispatches accordingly. A mesh of size 1 (or
``mesh=None``) routes through the exact single-device jit path, bit-for-bit
identical to pre-mesh behavior (tested in tests/test_sharded_parity.py).

Exactness: slice b of every batched result equals the single-document
engine run on document b (tested in tests/test_batch_serving.py), under
any mesh size (tests/test_sharded_parity.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.precision import pinned_precision
from repro.launch.sharding import serving_batch_sharding
from repro.serving.jit_engine import (
    JitIncrementalEngine, JitState, KVExport, jit_with_weights,
)

# A JitState whose every leaf carries a leading [B] document axis.
BatchedJitState = JitState


@jax.jit
def stack_states(states: list[JitState]) -> BatchedJitState:
    """Stack per-document states along a new leading batch axis. One jitted
    program per (batch size, state shape), named ``jit_stack_states`` in a
    profile: the whole-state copy each dispatch makes on the way in."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


@jax.jit
def unstack_state(batched: BatchedJitState, b: int) -> JitState:
    """Slice document ``b`` back out of a batched state. ``b`` is traced, so
    one program (``jit_unstack_state``) serves every row of a batch."""
    return jax.tree.map(lambda x: x[b], batched)


class BatchedJitEngine(JitIncrementalEngine):
    """vmap'd ``JitIncrementalEngine``: one fixed-shape step, B documents.

    Same constructor as the single-document engine (``edit_capacity``,
    ``row_capacity``, ``use_patch_kernel``, ``use_fused_kernel``,
    ``delta_threshold`` — the sigma-delta propagation gate of DESIGN.md §10,
    applied per document slice — ``_weights``), plus ``mesh`` /
    ``batch_axis`` for data-parallel sharding
    of the document axis. With ``use_fused_kernel=True`` each layer's patch
    + requantize runs as ONE batched ``fused_step`` Pallas launch (the
    batching rule turns the per-document kernel grid into a
    (doc, row-block, vq-head) grid).
    """

    def __init__(self, params, cfg, *, edit_capacity: int = 8,
                 row_capacity: int = 64, use_patch_kernel: bool = False,
                 use_fused_kernel: bool = False, delta_threshold: float = 0.0,
                 mesh: Optional[Mesh] = None, batch_axis: str = "data",
                 _weights=None):
        super().__init__(params, cfg, edit_capacity=edit_capacity,
                         row_capacity=row_capacity,
                         use_patch_kernel=use_patch_kernel,
                         use_fused_kernel=use_fused_kernel,
                         delta_threshold=delta_threshold, _weights=_weights)
        if mesh is not None:
            serving_batch_sharding(mesh, batch_axis)  # validates the axis
            # one replicated copy per device; a no-op for weights a sibling
            # engine on the same mesh already placed
            self.wts = jax.device_put(self.wts, NamedSharding(mesh, P()))
        self.mesh = mesh
        self.batch_axis = batch_axis
        self._sharded_fns: dict[str, callable] = {}

    @property
    def n_shards(self) -> int:
        """Devices the document axis splits across (1 = single-device path)."""
        return int(self.mesh.shape[self.batch_axis]) if self.mesh is not None else 1

    # ------------------------------------------------------------ shard plumbing

    def _check_batch(self, B: int) -> None:
        if B % self.n_shards != 0:
            raise ValueError(
                f"batch of {B} documents does not divide the serving mesh's "
                f"{self.n_shards}-way batch axis — pad the dispatch "
                "(BatchServer pads to a multiple automatically)")

    def _sharded(self, name: str):
        """jit(shard_map(vmapped impl)) taking ``(wts, *batched args)``: the
        weight pytree replicated (``P()``), every batched input/output leaf
        sharded on the batch axis (a single ``P(batch_axis)`` acts as the
        pytree-prefix spec for states, buckets and exports alike). Built
        lazily per entry point and cached per engine — one compiled step
        per (B, n_cap, C, R) exactly like the single-device path. The
        replication checker is off: it cannot always prove the per-shard
        outputs of a replicated-weight body."""
        fn = self._sharded_fns.get(name)
        if fn is None:
            batched = lambda impl: (  # noqa: E731
                lambda w, *a: jax.vmap(functools.partial(impl, w))(*a))
            builders = {
                "full_forward": (batched(self._full_forward_impl), 3),
                "apply_edits": (batched(self._apply_edits_impl), 5),
                "export_kv": (
                    lambda w, s: jax.vmap(self._export_kv_impl)(s), 1),
                "logits_at": (batched(self._logits_at_impl), 2),
            }
            body, n_args = builders[name]
            spec = serving_batch_sharding(self.mesh, self.batch_axis).spec
            fn = jax.jit(jax.shard_map(
                pinned_precision(body), mesh=self.mesh,
                in_specs=(P(),) + (spec,) * n_args,
                out_specs=spec, check_vma=False))
            self._sharded_fns[name] = fn
        return fn

    # ------------------------------------------------------------ batched API

    def batch_full_forward(self, tokens: jax.Array, positions: jax.Array,
                           valid: Optional[jax.Array] = None
                           ) -> BatchedJitState:
        """tokens/positions: [B, n] int32, valid: [B, n] bool (None = all
        real) → stacked state, leaves [B, ...]."""
        if self.n_shards > 1:
            self._check_batch(tokens.shape[0])
            if valid is None:
                valid = jnp.ones(tokens.shape, bool)
            return self._sharded("full_forward")(self.wts, tokens, positions,
                                                 valid)
        return self._batch_full_forward_local(tokens, positions, valid)

    @jit_with_weights
    def _batch_full_forward_local(self, wts, tokens, positions, valid=None):
        ff = functools.partial(self._full_forward_impl, wts)
        if valid is None:
            return jax.vmap(lambda t, p: ff(t, p))(tokens, positions)
        return jax.vmap(ff)(tokens, positions, valid)

    def batch_apply_edits(
        self, state: BatchedJitState, slot: jax.Array, tok: jax.Array,
        pos_id: jax.Array, op: jax.Array,
    ) -> tuple[BatchedJitState, jax.Array]:
        """slot/tok/pos_id/op: [B, C] int32 (pad unused slots with -1).
        Returns (new_state, overflow [B] bool). A document whose overflow
        flag is set exceeded its row bucket R at some layer; its slice is
        UNRELIABLE and the caller must re-run a full forward for it (the
        batch server's fallback + capacity-doubling policy)."""
        if self.n_shards > 1:
            self._check_batch(slot.shape[0])
            return self._sharded("apply_edits")(self.wts, state, slot, tok,
                                                pos_id, op)
        return self._batch_apply_edits_local(state, slot, tok, pos_id, op)

    @jit_with_weights
    def _batch_apply_edits_local(self, wts, state, slot, tok, pos_id, op):
        return jax.vmap(functools.partial(self._apply_edits_impl, wts))(
            state, slot, tok, pos_id, op)

    def batch_apply_replaces(
        self, state: BatchedJitState, edit_pos: jax.Array, edit_tok: jax.Array,
    ) -> tuple[BatchedJitState, jax.Array]:
        """Replace-only bucket: edit_pos/edit_tok [B, C] int32 (pad -1)."""
        z = jnp.zeros_like(edit_pos)
        return self.batch_apply_edits(state, edit_pos, edit_tok, z, z)

    def batch_apply_inserts(
        self, state: BatchedJitState, slot: jax.Array, tok: jax.Array,
        pos_id: jax.Array,
    ) -> tuple[BatchedJitState, jax.Array]:
        """Insert-only bucket: claim free slots with fresh mid-gap ids."""
        from repro.serving.jit_engine import OP_INSERT

        op = jnp.where(slot >= 0, OP_INSERT, 0).astype(slot.dtype)
        return self.batch_apply_edits(state, slot, tok, pos_id, op)

    def batch_apply_deletes(
        self, state: BatchedJitState, slot: jax.Array,
    ) -> tuple[BatchedJitState, jax.Array]:
        """Delete-only bucket: invalidate slots, subtract their columns."""
        from repro.serving.jit_engine import OP_DELETE

        z = jnp.zeros_like(slot)
        op = jnp.where(slot >= 0, OP_DELETE, 0).astype(slot.dtype)
        return self.batch_apply_edits(state, slot, z, z, op)

    def batch_export_kv(self, state: BatchedJitState) -> KVExport:
        """Position-ordered KV export for every document in the batch in one
        fused gather: each ``KVExport`` leaf gains a leading [B] axis.
        Parity-tested against the per-document ``export_kv`` — the batched
        entry point for a future bucket-batched suggestion refresh (the
        current scheduler exports per document as it refreshes)."""
        if self.n_shards > 1:
            self._check_batch(state.tokens.shape[0])
            return self._sharded("export_kv")(self.wts, state)
        return self._batch_export_kv_local(state)

    @functools.partial(jax.jit, static_argnums=0)
    def _batch_export_kv_local(self, state):
        return jax.vmap(self._export_kv_impl)(state)

    def batch_logits_at(self, state: BatchedJitState,
                        index: jax.Array) -> jax.Array:
        """index: [B] int32 per-document slot (the last-in-position-order
        valid slot for padded docs — the host scheduler tracks it)."""
        if self.n_shards > 1:
            self._check_batch(index.shape[0])
            return self._sharded("logits_at")(self.wts, state, index)
        return self._batch_logits_at_local(state, index)

    @jit_with_weights
    def _batch_logits_at_local(self, wts, state, index):
        return jax.vmap(functools.partial(self._logits_at_impl, wts))(
            state, index)
