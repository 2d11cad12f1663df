"""Scheduler/bucketing invariants for the batch server (ISSUE 1 satellites).

Three invariants, checked both property-based (hypothesis, via the
`_hypothesis_compat` shim) and with always-run deterministic seeds:

1. every submitted edit is applied exactly once;
2. every capacity the scheduler buckets by (n_cap, C, R) is a power of two;
3. final per-document token buffers equal the edit-replayed reference under
   random interleavings of submits and flushes.

The model here is tiny (smoke config) but real — dispatches go through the
vmapped jit engine, so these also exercise stacking/unstacking and the
overflow path under adversarial schedules.
"""
import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.vq_opt_125m import smoke_config
from repro.models import transformer as T
from repro.serving.batch_server import BatchServer


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(vqt=True)
    params = jax.device_get(T.init_params(jax.random.PRNGKey(1), cfg))
    return cfg, params


def _is_pow2(v: int) -> bool:
    return v >= 1 and (v & (v - 1)) == 0


def _run_interleaving(cfg, params, seed: int, n_docs: int, n_ops: int,
                      row_capacity: int = 16, max_batch: int = 3) -> None:
    """Random schedule of submits and flushes; assert all three invariants."""
    rng = np.random.default_rng(seed)
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=row_capacity,
                      max_batch=max_batch, min_doc_capacity=16)
    ref: dict[str, list[int]] = {}
    for i in range(n_docs):
        n = int(rng.integers(4, 36))
        toks = rng.integers(0, cfg.vocab, n)
        ref[f"d{i}"] = list(toks)
        srv.open_document(f"d{i}", toks)
    submitted = 0
    for _ in range(n_ops):
        if rng.random() < 0.25:
            srv.step()  # partial flush mid-stream
        else:
            did = f"d{int(rng.integers(n_docs))}"
            pos = int(rng.integers(len(ref[did])))
            tok = int(rng.integers(cfg.vocab))
            srv.submit_replace(did, pos, tok)
            ref[did][pos] = tok  # replay reference, submission order
            submitted += 1
    srv.flush()

    # invariant 1: exactly-once application
    assert srv.pending_count() == 0
    assert srv.stats.edits_submitted == submitted
    assert srv.stats.edits_applied == submitted

    # invariant 2: power-of-two capacities everywhere the scheduler buckets
    assert _is_pow2(srv.C)
    for doc in srv.docs.values():
        assert _is_pow2(doc.n_cap) and doc.n_cap >= doc.n
        assert _is_pow2(doc.row_capacity) and doc.row_capacity <= doc.n_cap
    for (C, R) in srv._engines:
        assert _is_pow2(C) and _is_pow2(R)

    # invariant 3: final buffers == edit-replayed references
    for did, toks in ref.items():
        assert list(srv.tokens(did)) == toks, did


# ------------------------------------------------------- deterministic seeds


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_interleaving_invariants_deterministic(setup, seed):
    cfg, params = setup
    _run_interleaving(cfg, params, seed=seed, n_docs=3, n_ops=30)


def test_conflicting_writes_same_position_fifo(setup):
    """Two queued writes to one position must land in submission order even
    though a single scatter bucket cannot hold both."""
    cfg, params = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      min_doc_capacity=16)
    rng = np.random.default_rng(3)
    toks = list(rng.integers(0, cfg.vocab, 20))
    srv.open_document("d", toks)
    with pytest.raises(ValueError):  # host buffer and device state must
        srv.submit_replace("d", 0, cfg.vocab)  # never see out-of-vocab tokens
    for tok in (5, 6, 7):  # three writes, same position
        srv.submit_replace("d", 10, tok)
    srv.submit_replace("d", 11, 8)
    assert srv.step() == 2  # (10,5) and the commuting (11,8) share a bucket
    assert srv.step() == 1  # (10,6) — same-position conflicts go one per round
    assert srv.step() == 1  # (10,7)
    assert srv.tokens("d")[10] == 7  # last writer won
    assert srv.tokens("d")[11] == 8
    assert srv.stats.batch_steps == 3


def test_capacity_overflow_doubles_to_pow2_and_converges(setup):
    """R=1 + wide edits: doubling must converge (R caps at n_cap, where
    overflow is impossible) and stay a power of two throughout."""
    cfg, params = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=1,
                      min_doc_capacity=16)
    rng = np.random.default_rng(4)
    toks = list(rng.integers(0, cfg.vocab, 16))
    srv.open_document("d", toks)
    for i in range(8):
        srv.submit_replace("d", i, int(rng.integers(cfg.vocab)))
        toks[i] = srv.docs["d"].pending[-1][2]  # (op, pos, tok)
    srv.flush()
    doc = srv.docs["d"]
    assert list(srv.tokens("d")) == toks
    assert _is_pow2(doc.row_capacity)
    assert doc.row_capacity <= doc.n_cap


def test_bucket_grouping_by_shape(setup):
    """Docs of different length buckets never share a dispatch; docs of the
    same bucket do (observable through mean batch size)."""
    cfg, params = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=8, min_doc_capacity=16)
    rng = np.random.default_rng(5)
    for i, n in enumerate((10, 12, 14, 60)):  # three n_cap=16, one n_cap=64
        srv.open_document(f"d{i}", rng.integers(0, cfg.vocab, n))
    for i in range(4):
        srv.submit_replace(f"d{i}", 1, 3)
    srv.step()
    # one dispatch for the 16-bucket trio + one for the 64-bucket doc
    assert srv.stats.batch_steps == 2
    assert srv.stats.batched_docs == 4


def test_failed_dispatch_restores_queue(setup, monkeypatch):
    """A dispatch that raises (device OOM, interrupt) must put every taken
    edit back at the front of its queue, in submission order."""
    cfg, params = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      min_doc_capacity=16)
    srv.open_document("d", list(range(1, 17)))
    srv.submit_replace("d", 2, 9)
    srv.submit_replace("d", 5, 4)
    eng = srv.engine(srv.C, srv.docs["d"].row_capacity)

    def boom(*args, **kwargs):
        raise RuntimeError("simulated device failure")

    monkeypatch.setattr(eng, "batch_apply_replaces", boom)
    with pytest.raises(RuntimeError, match="simulated device failure"):
        srv.step()
    assert list(srv.docs["d"].pending) == [("replace", 2, 9), ("replace", 5, 4)]
    assert srv.stats.edits_applied == 0 and srv.stats.batch_steps == 0
    monkeypatch.undo()
    srv.flush()
    toks = srv.tokens("d")
    assert toks[2] == 9 and toks[5] == 4


def _gap_profile(alloc):
    return [alloc.gap_at(i) for i in range(len(alloc) + 1)]


def test_failed_dispatch_rollback_no_allocator_leak(setup, monkeypatch):
    """A rolled-back failed dispatch must restore the affected documents'
    ``PositionAllocator`` gap state exactly — even when the take itself ran
    a defrag (id re-spread + re-ingest) first — and must not leak any gap
    state into documents placed on other shard rows of the same dispatch or
    not dispatched at all (ISSUE 4 satellite). Runs over a 2-shard mesh when
    the environment has the devices (the CI test-multidevice job), else
    single-device — the rollback path is identical."""
    import jax

    from repro.launch.mesh import make_serving_mesh

    cfg, params = setup
    mesh = make_serving_mesh(min(2, jax.device_count()))
    # pool of 16 over 8 tokens: the gap at one insertion point survives
    # exactly one insert, so the second take at the same point must defrag
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=4, min_doc_capacity=16, pos_pool=16,
                      mesh=mesh)
    ref = {d: list(range(1, 9)) for d in ("a", "b", "c")}
    for d, toks in ref.items():
        srv.open_document(d, toks)
    srv.submit_insert("a", 3, 5)
    ref["a"].insert(3, 5)
    srv.flush()  # consumes doc a's gap at sequence index 3

    pre = {d: srv.docs[d].allocator.snapshot().copy() for d in ref}
    pre_gaps = {d: _gap_profile(srv.docs[d].allocator) for d in ref}
    srv.submit_insert("a", 3, 6)  # gap exhausted: the take defrags first
    srv.submit_insert("b", 0, 7)  # same dispatch group, different shard row
    ref["a"].insert(3, 6)
    ref["b"].insert(0, 7)
    eng = srv.engine(srv.C, srv.docs["a"].row_capacity)
    monkeypatch.setattr(
        eng, "batch_apply_inserts",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("simulated device failure")))
    applied_before = srv.stats.edits_applied
    with pytest.raises(RuntimeError, match="simulated device failure"):
        srv.step()
    assert srv.stats.defrags >= 1  # the take really exercised the slow path

    # every allocator is back to its pre-take gap state: the defragged doc
    # rolled back to pre-defrag ids, its dispatch-mates and idle docs are
    # untouched
    for d in ref:
        np.testing.assert_array_equal(srv.docs[d].allocator.snapshot(),
                                      pre[d])
        assert _gap_profile(srv.docs[d].allocator) == pre_gaps[d]
    assert list(srv.docs["a"].pending) == [("insert", 3, 6)]
    assert list(srv.docs["b"].pending) == [("insert", 0, 7)]
    assert srv.stats.edits_applied == applied_before

    monkeypatch.undo()
    srv.flush()  # the retry re-defrags and applies everything exactly once
    for d, toks in ref.items():
        assert list(srv.tokens(d)) == toks, d


# ------------------------------------------------------------ property-based


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n_docs=st.integers(1, 4),
       n_ops=st.integers(1, 40))
def test_interleaving_invariants_property(setup, seed, n_docs, n_ops):
    cfg, params = setup
    _run_interleaving(cfg, params, seed=seed, n_docs=n_docs, n_ops=n_ops)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), row_capacity=st.sampled_from([1, 2, 4]))
def test_tight_capacity_property(setup, seed, row_capacity):
    """Under overflow-heavy schedules the invariants must still hold."""
    cfg, params = setup
    _run_interleaving(cfg, params, seed=seed, n_docs=2, n_ops=16,
                      row_capacity=row_capacity, max_batch=2)


# ------------------------------------------ one dispatch in flight per step


def _chunked_server(cfg, params, max_batch):
    """Four documents in one (n_cap, C, R) group and row capacity 2: the
    first chunk's wide edits overflow."""
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=2,
                      max_batch=max_batch, min_doc_capacity=16)
    rng = np.random.default_rng(11)
    for i in range(4):
        srv.open_document(f"d{i}", rng.integers(0, cfg.vocab, 20 + 2 * i))
    return srv


def _edits_for(i):
    """Documents 0 and 1 edit their first slots (every row after them is
    dirty), 2 and 3 their last ones."""
    pos = (0, 1, 2) if i < 2 else (17, 18, 19)
    return [(p, (7 * i + p) % 32) for p in pos]


def _assert_bitwise_equal(a, b, doc_ids):
    for d in doc_ids:
        assert list(a.tokens(d)) == list(b.tokens(d)), d
        for x, y in zip(jax.tree.leaves(a.state(d)),
                        jax.tree.leaves(b.state(d))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        np.testing.assert_array_equal(a.logits(d), b.logits(d))


@pytest.mark.parametrize("max_batch", [1, 2])
def test_pipelined_step_matches_serial_dispatches(setup, max_batch):
    """A step of several chunks launches chunk k+1 before it syncs and
    adopts chunk k, whose overflow re-ingest then runs behind it. Every
    state and logit equals the same chunks served one step() each, which
    runs the same programs on the same inputs in the old order."""
    cfg, params = setup
    ids = [f"d{i}" for i in range(4)]
    piped = _chunked_server(cfg, params, max_batch)
    serial = _chunked_server(cfg, params, max_batch)
    for i, d in enumerate(ids):
        for p, t in _edits_for(i):
            piped.submit_replace(d, p, t)
    assert piped.step() == 12
    n_chunks = 4 // max_batch
    assert piped.stats.batch_steps == n_chunks
    assert piped.stats.overlapped_dispatches == n_chunks - 1
    for lo in range(0, 4, max_batch):  # one chunk per step: no overlap
        for i in range(lo, lo + max_batch):
            for p, t in _edits_for(i):
                serial.submit_replace(ids[i], p, t)
        serial.step()
    assert serial.stats.overlapped_dispatches == 0
    assert piped.docs["d0"].row_capacity > 2  # the first chunk overflowed
    assert piped.stats.overflows == serial.stats.overflows >= 1
    _assert_bitwise_equal(piped, serial, ids)


def test_failed_launch_with_a_dispatch_in_flight_loses_no_edit(
        setup, monkeypatch):
    """The launch of chunk 2 raises while chunk 1 is launched and not yet
    synced: chunk 1 is not adopted, every document rolls back to its
    snapshot with its device state untouched, and the retry matches a
    server that never failed."""
    cfg, params = setup
    ids = [f"d{i}" for i in range(4)]
    srv = _chunked_server(cfg, params, 1)
    clean = _chunked_server(cfg, params, 1)
    for s in (srv, clean):
        for i, d in enumerate(ids):
            for p, t in _edits_for(i):
                s.submit_replace(d, p, t)
    before = {d: (srv.docs[d].state, srv.docs[d].seq_tokens().copy(),
                  list(srv.docs[d].pending)) for d in ids}
    eng = srv.engine(srv.C, srv.R)
    real, calls = eng.batch_apply_replaces, []

    def second_fails(*args):
        calls.append(len(calls))
        if len(calls) == 2:
            raise RuntimeError("simulated device failure")
        return real(*args)

    monkeypatch.setattr(eng, "batch_apply_replaces", second_fails)
    with pytest.raises(RuntimeError, match="simulated device failure"):
        srv.step()
    assert srv.stats.edits_applied == 0 and srv.stats.batch_steps == 0
    for d in ids:
        state, toks, pending = before[d]
        assert srv.docs[d].state is state, d
        assert list(srv.docs[d].seq_tokens()) == list(toks), d
        assert list(srv.docs[d].pending) == pending, d
    monkeypatch.undo()
    assert srv.flush() == clean.flush() == 12
    _assert_bitwise_equal(srv, clean, ids)


@pytest.mark.parametrize("seed,max_batch", [(0, 1), (1, 2)])
def test_overlapped_dispatches_count(setup, seed, max_batch):
    """Every dispatch but the first of its step() is launched with an
    earlier one in flight, over a mixed stream of several op groups."""
    cfg, params = setup
    rng = np.random.default_rng(seed)
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=max_batch, min_doc_capacity=16)
    ref = {f"d{i}": list(rng.integers(0, cfg.vocab, 10 + 4 * i))
           for i in range(4)}
    for d, toks in ref.items():
        srv.open_document(d, toks)
    steps_with_dispatch = 0
    for _ in range(6):
        for d, r in ref.items():
            kind = rng.choice(["replace", "insert", "delete"])
            p = int(rng.integers(len(r)))
            t = int(rng.integers(cfg.vocab))
            if kind == "insert":
                srv.submit_insert(d, p, t)
                r.insert(p, t)
            elif kind == "delete":
                srv.submit_delete(d, p)
                del r[p]
            else:
                srv.submit_replace(d, p, t)
                r[p] = t
        while srv.pending_count():
            n = srv.stats.batch_steps
            srv.step()
            steps_with_dispatch += srv.stats.batch_steps > n
    assert srv.stats.overlapped_dispatches == (
        srv.stats.batch_steps - steps_with_dispatch) > 0
    for d, r in ref.items():
        assert list(srv.tokens(d)) == r, d
