"""The one place the benchmark touches the program: it builds the system
under test from a configuration file, warms the shapes a cell serves, and
wraps the public calls of the instances it built in profiler spans for a
traced run."""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


def build_server(params, arch, serving: dict):
    """``BatchServer`` under ``AsyncBatchServer`` for the program's
    ``ArchConfig`` ``arch``, as the configuration file's ``serving`` group
    sets them."""
    from repro.serving.async_server import AsyncBatchServer
    from repro.serving.batch_server import BatchServer

    srv = BatchServer(
        params, arch, edit_capacity=serving["edit_capacity"],
        row_capacity=serving["row_capacity"], max_batch=serving["max_batch"],
        capacity_class_step=serving["capacity_class_step"],
        delta_threshold=serving["delta_threshold"])
    asrv = AsyncBatchServer(
        srv, max_batch_delay_ms=serving["max_batch_delay_ms"],
        bucket_docs=serving["bucket_docs"])
    return srv, asrv


def _pow2_upto(lo: int, hi: int) -> list:
    out, v = [], lo
    while v < hi:
        out.append(v)
        v *= 2
    return out + [hi]


def warm_shapes(srv, doc_ids: list, subscribe: int) -> None:
    """Run every program a cell of these documents can reach, once, at the
    documents' capacity class: the batched edit step at each padded batch
    size and each row capacity an overflow can double to, the stacking
    around it, the re-ingest and defrag programs, and (with subscriptions)
    the suggestion prefill at every chunk length and the decode steps;
    and the small ops that the replace, insert and delete buckets add."""
    import jax
    import jax.numpy as jnp

    from repro.serving.batch_engine import stack_states, unstack_state

    caps = sorted({srv.docs[d].n_cap for d in doc_ids})
    batches = _pow2_upto(1, srv.max_batch)
    for n_cap in caps:
        base = srv.engine(srv.C, srv.R)
        doc = next(d for d in doc_ids if srv.docs[d].n_cap == n_cap)
        one = srv.state(doc)
        jax.block_until_ready(base.gather_slots(
            one, jnp.arange(n_cap, dtype=jnp.int32)))
        jax.block_until_ready(base.full_forward(
            jnp.zeros(n_cap, jnp.int32), jnp.arange(n_cap, dtype=jnp.int32),
            jnp.ones(n_cap, bool)))
        empty = jnp.full((max(batches), srv.C), -1, jnp.int32)
        for R in _pow2_upto(min(srv.R, n_cap), n_cap):
            eng = srv.engine(srv.C, R)
            for B in batches:
                stacked = stack_states([one] * B)
                out, overflow = eng.batch_apply_edits(
                    stacked, empty[:B], empty[:B] * 0, empty[:B] * 0,
                    empty[:B] * 0)
                del stacked
                np.asarray(overflow)
                for b in range(B):
                    jax.block_until_ready(unstack_state(out, b))
                del out
                if R == srv.R:  # the small ops each kind's bucket adds
                    e = np.asarray(empty[:B])
                    for call in (lambda s: eng.batch_apply_replaces(s, e, e),
                                 lambda s: eng.batch_apply_inserts(s, e, e, e),
                                 lambda s: eng.batch_apply_deletes(s, e)):
                        np.asarray(call(stack_states([one] * B))[1])
        if subscribe:
            st = srv.state(doc)
            n = int(st.n_real)
            valid, pos = np.asarray(st.valid), np.asarray(st.positions)
            seq = np.sort(pos[valid])
            for M in _pow2_upto(1, n_cap):
                p = max(n - M, 0)
                srv.suggester.refresh(base, st, key=None, n_new=subscribe,
                                      export_invalid_from=int(seq[p]))


def counters(srv, asrv) -> dict:
    """Every numeric counter of the server, the front end and the
    suggester, under ``<owner>.<field>``."""
    out = {}
    for owner, obj in (("batch", srv.stats), ("async", asrv.stats),
                       ("suggest", srv.suggest_stats)):
        for f in dataclasses.fields(obj):
            v = getattr(obj, f.name)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"{owner}.{f.name}"] = v
    return out


class Spans:
    """Profiler spans around the public calls of the instances a traced run
    built, and a record of each edit dispatch's real sizes (documents with
    edits, their real lengths and edits by kind) for the work counts."""

    def __init__(self, srv):
        import jax

        self.dispatches = []
        self._annotate = jax.profiler.TraceAnnotation
        self.recording = False
        srv.flush = self._wrap(srv.flush, "bench.server.flush")
        srv.suggester.refresh = self._wrap(srv.suggester.refresh,
                                           "bench.suggest.refresh")
        get_engine = srv.engine

        @functools.wraps(get_engine)
        def engine(*args, **kwargs):
            eng = get_engine(*args, **kwargs)
            if not getattr(eng, "_bench_wrapped", False):
                eng.batch_apply_edits = self._edit_step(eng.batch_apply_edits)
                eng._bench_wrapped = True
            return eng

        srv.engine = engine

    def _wrap(self, fn, name):
        annotate = self._annotate

        @functools.wraps(fn)
        def call(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)

        return call

    def _edit_step(self, fn):
        annotate = self._annotate

        @functools.wraps(fn)
        def call(state, slot, tok, pos_id, op):
            with annotate("bench.engine.batch_apply_edits"):
                out = fn(state, slot, tok, pos_id, op)
            if self.recording:
                slots, ops = np.asarray(slot), np.asarray(op)
                n_real = np.asarray(state.n_real)
                docs = []
                for b in range(slots.shape[0]):
                    live = slots[b] >= 0
                    if live.any():
                        kinds = ops[b][live]
                        docs.append((int(n_real[b]), int((kinds == 0).sum()),
                                     int((kinds == 1).sum()),
                                     int((kinds == 2).sum())))
                self.dispatches.append(docs)
            return out

        return call
