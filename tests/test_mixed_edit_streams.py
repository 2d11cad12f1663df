"""Full edit algebra in the jit path == the NumPy engine (ISSUE 2 tentpole).

Parity ladder over randomized insert/delete/replace streams:

1. engine level — the slot-buffer ``JitIncrementalEngine`` stepped edit by
   edit (host-managed slot map) matches ``IncrementalEngine`` in sequence
   order: codes exact, activations to float tolerance;
2. server level — ``BatchServer`` serves a randomized mixed stream (>=30%
   structural edits) end to end with fixed-shape dispatches only (the
   traced-shape count is bounded by the capacity grid, not the edit
   count), and the final states match a NumPy full forward on the same
   sequence-ordered tokens/positions;
3. forced gap exhaustion — a tiny position pool drives the allocator into
   defragmentation (full-forward re-ingest), after which parity holds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vq_opt_125m import smoke_config
from repro.core.incremental import IncrementalEngine
from repro.models import transformer as T
from repro.serving.batch_server import BatchServer
from repro.serving.jit_engine import JitIncrementalEngine


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(vqt=True)
    params = jax.device_get(T.init_params(jax.random.PRNGKey(1), cfg))
    jeng = JitIncrementalEngine(params, cfg, edit_capacity=4, row_capacity=16)
    neng = IncrementalEngine(params, cfg)
    return cfg, params, jeng, neng


def _assert_seq_parity(js, slots, ns, neng, atol=3e-4):
    sl = np.asarray(slots)
    np.testing.assert_array_equal(np.asarray(js.tokens)[sl], ns.tokens)
    np.testing.assert_array_equal(np.asarray(js.positions)[sl], ns.positions)
    assert int(js.n_real) == ns.n
    for li in range(len(neng.layers)):
        np.testing.assert_array_equal(np.asarray(js.codes[li])[sl],
                                      ns.layers[li].codes)
    np.testing.assert_allclose(np.asarray(js.x[-1])[sl], ns.xs[-1], atol=atol)


# ------------------------------------------------------------- engine level


def test_engine_mixed_stream_matches_numpy(setup):
    """Randomized insert/delete/replace stream, one jit step per edit, with
    slot reuse (deleted slots are reclaimed by later inserts)."""
    cfg, params, jeng, neng = setup
    rng = np.random.default_rng(0)
    n, n_cap, pool = 12, 16, 2048
    tokens = np.zeros(n_cap, np.int32)
    tokens[:n] = rng.integers(0, cfg.vocab, n)
    positions = np.full(n_cap, pool - 1, np.int32)
    positions[:n] = (np.arange(1, n + 1) * pool) // (n + 1)
    valid = np.zeros(n_cap, bool)
    valid[:n] = True
    slots = list(range(n))
    free = list(range(n_cap - 1, n - 1, -1))
    pad = jnp.asarray([-1, -1, -1], jnp.int32)

    js = jeng.full_forward(jnp.asarray(tokens), jnp.asarray(positions),
                           jnp.asarray(valid))
    ns = neng.full_forward(tokens[:n], positions[:n])
    _assert_seq_parity(js, slots, ns, neng)

    structural = 0
    for step in range(24):
        kind = rng.choice(["replace", "insert", "delete"])
        nn = len(slots)
        if kind == "insert" and free:
            p = int(rng.integers(nn + 1))
            t = int(rng.integers(cfg.vocab))
            lo = ns.positions[p - 1] if p > 0 else -1
            hi = ns.positions[p] if p < nn else pool
            if hi - lo <= 1:
                continue
            pid = int((lo + hi) // 2)
            s = free.pop()
            slots.insert(p, s)
            js, ovf = jeng.apply_inserts(
                js, jnp.concatenate([jnp.asarray([s], jnp.int32), pad]),
                jnp.asarray([t, 0, 0, 0], jnp.int32),
                jnp.asarray([pid, 0, 0, 0], jnp.int32))
            ns = neng.apply_insert(ns, p, t, pid)
            structural += 1
        elif kind == "delete" and nn > 2:
            p = int(rng.integers(nn))
            s = slots.pop(p)
            free.append(s)
            js, ovf = jeng.apply_deletes(
                js, jnp.concatenate([jnp.asarray([s], jnp.int32), pad]))
            ns = neng.apply_delete(ns, p)
            structural += 1
        else:
            p = int(rng.integers(nn))
            t = int(rng.integers(cfg.vocab))
            js, ovf = jeng.apply_replaces(
                js, jnp.concatenate([jnp.asarray([slots[p]], jnp.int32), pad]),
                jnp.asarray([t, 0, 0, 0], jnp.int32))
            ns = neng.apply_replaces(ns, [p], [t])
        assert not bool(ovf), (step, kind)
        _assert_seq_parity(js, slots, ns, neng)
    assert structural >= 5  # the stream genuinely exercised inserts/deletes


def test_engine_mixed_bucket_single_step(setup):
    """One generic apply_edits step carrying a replace AND an insert."""
    cfg, params, jeng, neng = setup
    rng = np.random.default_rng(3)
    n, n_cap, pool = 10, 16, 2048
    tokens = np.zeros(n_cap, np.int32)
    tokens[:n] = rng.integers(0, cfg.vocab, n)
    positions = np.full(n_cap, pool - 1, np.int32)
    positions[:n] = (np.arange(1, n + 1) * pool) // (n + 1)
    valid = np.zeros(n_cap, bool)
    valid[:n] = True
    js = jeng.full_forward(jnp.asarray(tokens), jnp.asarray(positions),
                           jnp.asarray(valid))
    ns = neng.full_forward(tokens[:n], positions[:n])
    pid = int((positions[4] + positions[5]) // 2)
    slots = list(range(n))
    slots.insert(5, 10)  # fresh slot for the insert
    js, ovf = jeng.apply_edits(
        js,
        jnp.asarray([2, 10, -1, -1], jnp.int32),  # slot
        jnp.asarray([7, 9, 0, 0], jnp.int32),  # tok
        jnp.asarray([0, pid, 0, 0], jnp.int32),  # pos_id
        jnp.asarray([0, 1, 0, 0], jnp.int32),  # op: replace, insert
    )
    assert not bool(ovf)
    ns = neng.apply_replaces(ns, [2], [7])
    ns = neng.apply_insert(ns, 5, 9, pid)
    _assert_seq_parity(js, slots, ns, neng)


# ------------------------------------------------------------- server level


def test_server_mixed_stream_parity_and_fixed_shapes(setup):
    """BatchServer serves a >=30%-structural randomized stream end to end;
    every dispatch is fixed-shape (traced-shape count independent of the
    edit count) and final states match the NumPy engine."""
    cfg, params, jeng, neng = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=4, min_doc_capacity=16, pos_pool=2048)
    rng = np.random.default_rng(6)
    ref = {}
    for i in range(3):
        n = int(rng.integers(10, 15))
        toks = rng.integers(0, cfg.vocab, n)
        ref[f"d{i}"] = list(toks)
        srv.open_document(f"d{i}", toks)
    n_ops, structural = 48, 0
    for _ in range(n_ops):
        did = f"d{int(rng.integers(3))}"
        r = ref[did]
        kind = rng.choice(["replace", "insert", "delete"], p=[0.5, 0.3, 0.2])
        if kind == "insert":
            p = int(rng.integers(len(r) + 1))
            t = int(rng.integers(cfg.vocab))
            srv.submit_insert(did, p, t)
            r.insert(p, t)
            structural += 1
        elif kind == "delete" and len(r) > 1:
            p = int(rng.integers(len(r)))
            srv.submit_delete(did, p)
            del r[p]
            structural += 1
        else:
            p = int(rng.integers(len(r)))
            t = int(rng.integers(cfg.vocab))
            srv.submit_replace(did, p, t)
            r[p] = t
        if rng.random() < 0.3:
            srv.step()  # partial flush mid-stream
    srv.flush()
    assert structural / n_ops >= 0.3
    assert srv.pending_count() == 0
    assert srv.stats.edits_applied == srv.stats.edits_submitted
    # fixed-shape serving: shapes come from the capacity grid (n_cap
    # buckets x batch pads x full/edit), never from individual edits —
    # far fewer traced shapes than edits applied
    assert srv.stats.rejits <= 8
    for did, r in ref.items():
        assert list(srv.tokens(did)) == r, did
        doc = srv.docs[did]
        ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
        _assert_seq_parity(doc.state, doc.slots, ns, neng)
        np.testing.assert_allclose(srv.logits(did), neng.logits_at(ns),
                                   atol=3e-4)


def test_server_gap_exhaustion_defrags_and_recovers(setup):
    """A tiny position pool forces gap exhaustion: the scheduler must
    defragment (re-spread ids + full-forward re-ingest) and stay exact."""
    cfg, params, jeng, neng = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=2, min_doc_capacity=16, pos_pool=64)
    rng = np.random.default_rng(7)
    r = list(rng.integers(0, cfg.vocab, 8))
    srv.open_document("d", r)
    # hammer one insertion point: each insert halves the local gap, so a
    # pool of 64 exhausts within a handful of inserts
    for _ in range(8):
        t = int(rng.integers(cfg.vocab))
        srv.submit_insert("d", 3, t)
        r.insert(3, t)
        srv.flush()
    assert srv.stats.defrags >= 1
    assert srv.docs["d"].allocator.defrag_count >= 1
    assert list(srv.tokens("d")) == r
    doc = srv.docs["d"]
    ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
    _assert_seq_parity(doc.state, doc.slots, ns, neng)


def test_server_capacity_grow_on_full_buffer(setup):
    """Inserting past n_cap steps the slot buffer up to the next capacity
    class (on-device pad, no re-ingest) without losing exactness."""
    cfg, params, jeng, neng = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=2, min_doc_capacity=8, pos_pool=2048)
    rng = np.random.default_rng(8)
    r = list(rng.integers(0, cfg.vocab, 7))
    srv.open_document("d", r)
    assert srv.docs["d"].n_cap == 8
    for i in range(6):
        t = int(rng.integers(cfg.vocab))
        p = int(rng.integers(len(r) + 1))
        srv.submit_insert("d", p, t)
        r.insert(p, t)
    srv.flush()
    doc = srv.docs["d"]
    assert srv.stats.grows >= 1
    assert srv.stats.device_grows >= 1
    assert doc.n_cap == srv.padded_cap(9) and doc.n == 13
    assert list(srv.tokens("d")) == r
    ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
    _assert_seq_parity(doc.state, doc.slots, ns, neng)


def test_server_edit_script_round_trip(setup):
    """submit_edit consumes core.edits scripts: replaying a random revision
    through the server reproduces the revision exactly."""
    from repro.core.edits import apply_edits, edit_script, random_revision

    cfg, params, jeng, neng = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      min_doc_capacity=16, pos_pool=2048)
    rng = np.random.default_rng(9)
    base = list(rng.integers(0, cfg.vocab, 12))
    srv.open_document("d", base)
    new = random_revision(rng, base, cfg.vocab, edit_fraction=0.3)
    script = edit_script(base, new)
    for e in script:
        srv.submit_edit("d", e)
    srv.flush()
    assert list(srv.tokens("d")) == apply_edits(base, script) == list(new)


def test_server_long_mixed_stream_compiled_shape_budget(setup):
    """ISSUE 7 satellite: a LONG mixed stream (structural-heavy, crossing a
    capacity-class boundary) must stay within a fixed compiled-shape
    budget, and the per-edit launch rate must stay O(1) — the ragged
    capacity classes + device-side grow keep the shape lattice bounded by
    the class grid, never by traffic volume."""
    cfg, params, jeng, neng = setup
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=2, min_doc_capacity=8, pos_pool=2048)
    rng = np.random.default_rng(11)
    ref = {f"d{i}": list(rng.integers(0, cfg.vocab, 6)) for i in range(2)}
    srv.open_documents({k: list(v) for k, v in ref.items()})
    n_ops = 96
    for _ in range(n_ops):
        did = f"d{int(rng.integers(2))}"
        r = ref[did]
        kind = rng.choice(["replace", "insert", "delete"], p=[0.4, 0.4, 0.2])
        if kind == "insert":
            p = int(rng.integers(len(r) + 1))
            t = int(rng.integers(cfg.vocab))
            srv.submit_insert(did, p, t)
            r.insert(p, t)
        elif kind == "delete" and len(r) > 1:
            p = int(rng.integers(len(r)))
            srv.submit_delete(did, p)
            del r[p]
        else:
            p = int(rng.integers(len(r)))
            t = int(rng.integers(cfg.vocab))
            srv.submit_replace(did, p, t)
            r[p] = t
        if rng.random() < 0.5:
            srv.step()
    srv.flush()
    assert srv.stats.grows >= 1  # the stream DID cross a class boundary
    # the budget: ingest shapes + one edit shape per visited (class, B pad)
    # + one pad shape per class transition + overflow/defrag full shapes.
    # 2 classes x {full, edit, pad} at <= 2 batch pads is well under 12 —
    # and crucially INDEPENDENT of n_ops (96 edits here, was 8 shapes at
    # 24 edits in dev runs)
    assert srv.stats.traced_shapes <= 12
    assert srv.stats.traced_shapes == srv.stats.rejits  # alias stays true
    for did, r in ref.items():
        assert list(srv.tokens(did)) == r, did
        doc = srv.docs[did]
        ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
        _assert_seq_parity(doc.state, doc.slots, ns, neng)
