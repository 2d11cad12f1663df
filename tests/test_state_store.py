"""Tiered document-state store (ISSUE 5): eviction, persistence, rehydration.

The contract under test (DESIGN.md §7): device state is a pure function of
its snapshot, so a document that was evicted to host RAM (warm) or disk
(cold) and touched again is **bit-exact** against one that never left the
device — rehydration is a re-upload, never a recompute. Suggestion decode
caches are soft state: dropping them changes nothing token-level. And
``close_document`` is the true inverse of ``open_document``: open→edit→
suggest→close churn leaks no slots, no allocator state, no caches, no bytes.
"""
import os

import jax
import numpy as np
import pytest

from repro.configs.vq_opt_125m import smoke_config
from repro.models import transformer as T
from repro.serving.batch_server import BatchServer
from repro.serving.jit_engine import (
    state_nbytes, state_nbytes_for, state_from_host, state_to_host,
)
from repro.serving.state_store import DeviceBudgetError

DOC_LEN = 12
N_CAP = 16  # next_pow2(DOC_LEN, min_doc_capacity=16)


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(vqt=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("edit_capacity", 4)
    kw.setdefault("row_capacity", 32)
    kw.setdefault("max_batch", 2)
    kw.setdefault("min_doc_capacity", 16)
    return BatchServer(params, cfg, **kw)


def _open_docs(srv, cfg, n_docs, seed=0, doc_len=DOC_LEN):
    rng = np.random.default_rng(seed)
    docs = {f"d{i}": list(rng.integers(0, cfg.vocab, doc_len))
            for i in range(n_docs)}
    srv.open_documents({d: list(t) for d, t in docs.items()})
    return docs


def _doc_bytes(srv):
    eng = srv.engine(srv.C, srv.R)
    return state_nbytes_for(N_CAP, eng.L, eng.meta)


def _reconcile(srv):
    """Recount every byte/doc stat from the underlying objects and assert
    the store-maintained counters match exactly (the BatchStats memory-
    blindness satellite)."""
    s = srv.stats
    tiers = srv.store.tiers()
    assert set(tiers) == set(srv.docs)
    hot = [d for d, t in tiers.items() if t == "hot"]
    warm = [d for d, t in tiers.items() if t == "warm"]
    cold = [d for d, t in tiers.items() if t == "cold"]
    assert (s.docs_hot, s.docs_warm, s.docs_cold) == \
        (len(hot), len(warm), len(cold))
    assert s.bytes_hot == sum(state_nbytes(srv.docs[d].state) for d in hot)
    for d in hot:
        assert srv.docs[d].state is not None
    for d in warm + cold:
        assert srv.docs[d].state is None
    assert s.bytes_warm == sum(srv.store.nbytes(d) for d in warm)
    assert s.bytes_cold == sum(srv.store.nbytes(d) for d in cold)
    if srv._sugg is not None:
        assert s.bytes_suggest == sum(
            srv._sugg.cache_nbytes(k) for k in srv._sugg.cached_keys())
    else:
        assert s.bytes_suggest == 0
    assert s.state_touches == s.hot_hits + s.rehydrations + s.rollback_rebuilds


# ---------------------------------------------------------------- accounting


def test_state_nbytes_formula_matches(setup):
    cfg, params = setup
    srv = _server(cfg, params)
    _open_docs(srv, cfg, 1)
    doc = srv.docs["d0"]
    eng = srv.engine(srv.C, srv.R)
    assert state_nbytes(doc.state) == state_nbytes_for(
        doc.n_cap, eng.L, eng.meta)
    _reconcile(srv)


def test_stats_reconcile(setup, tmp_path):
    """Byte/doc counters reconcile after every kind of movement: ingest,
    edits, suggestion caches, forced warm and cold evictions, rehydration,
    grow (an n_cap-doubling re-ingest changes the footprint), close."""
    cfg, params = setup
    srv = _server(cfg, params, spill_dir=str(tmp_path))
    _open_docs(srv, cfg, 3)
    _reconcile(srv)
    srv.submit_replace("d0", 2, 5)
    srv.submit_insert("d1", 0, 9)
    srv.flush()
    _reconcile(srv)
    srv.suggest("d0", 4)
    srv.suggest("d1", 4)
    _reconcile(srv)
    assert srv.stats.bytes_suggest > 0
    srv.evict("d0", "warm")
    _reconcile(srv)
    assert srv.stats.evictions == 1
    srv.evict("d1", "cold")
    _reconcile(srv)
    assert srv.stats.spills == 1 and srv.stats.bytes_cold > 0
    srv.submit_replace("d1", 1, 3)  # cold doc: next dispatch rehydrates
    srv.flush()
    _reconcile(srv)
    assert srv.tier("d1") == "hot" and srv.stats.rehydrations >= 1
    # grow d2 past its slot capacity: the doubled footprint is recounted
    before = srv.store.nbytes("d2")
    for i in range(N_CAP):
        srv.submit_insert("d2", 0, 1)
    srv.flush()
    eng = srv.engine(srv.C, srv.R)
    assert srv.stats.grows >= 1
    assert srv.store.nbytes("d2") == state_nbytes_for(
        srv.docs["d2"].n_cap, eng.L, eng.meta) > before
    _reconcile(srv)
    srv.close_document("d0")
    srv.close_document("d1")
    srv.close_document("d2")
    _reconcile(srv)
    assert srv.stats.bytes_hot == srv.stats.bytes_warm == \
        srv.stats.bytes_cold == srv.stats.bytes_suggest == 0


def test_close_document_no_leak(setup):
    """open→edit→suggest→close in a loop at small capacity grows nothing:
    no document objects, no store entries, no suggestion caches, no bytes —
    and a long-lived bystander document's allocator and slot map are
    untouched (extends the PR 4 allocator rollback leak test)."""
    cfg, params = setup
    srv = _server(cfg, params)
    _open_docs(srv, cfg, 1, seed=7)  # the long-lived bystander
    srv.suggest("d0", 4)
    base = srv.docs["d0"]
    base_alloc = base.allocator.snapshot().copy()
    base_free = list(base.free)
    baseline = (srv.stats.bytes_hot, len(srv.docs),
                len(srv.suggester.cached_keys()))
    rng = np.random.default_rng(3)
    for i in range(4):
        did = f"churn{i}"
        srv.open_document(did, list(rng.integers(0, cfg.vocab, DOC_LEN)))
        srv.submit_insert(did, 0, 2)
        srv.submit_replace(did, 3, 4)
        srv.submit_delete(did, 1)
        srv.flush()
        srv.suggest(did, 4)
        srv.close_document(did)
        assert (srv.stats.bytes_hot, len(srv.docs),
                len(srv.suggester.cached_keys())) == baseline
        assert did not in srv.store
        _reconcile(srv)
    assert srv.stats.closes == 4
    np.testing.assert_array_equal(base.allocator.snapshot(), base_alloc)
    assert list(base.free) == base_free
    with pytest.raises(KeyError):
        srv.close_document("churn0")  # double-close / unknown id


# ---------------------------------------------------------------- residency


def test_rehydration_is_bit_exact(setup, tmp_path):
    """Warm and cold round-trips reproduce logits and state leaves bit-for-
    bit — no recompute, no float drift."""
    cfg, params = setup
    srv = _server(cfg, params, spill_dir=str(tmp_path))
    _open_docs(srv, cfg, 2, seed=1)
    srv.submit_insert("d0", 2, 11)
    srv.flush()
    ref_logits = srv.logits("d0")
    ref_state = state_to_host(srv.docs["d0"].state)
    for tier in ("warm", "cold"):
        srv.evict("d0", tier)
        assert srv.tier("d0") == tier and srv.docs["d0"].state is None
        got = srv.logits("d0")  # transparent rehydration on touch
        assert srv.tier("d0") == "hot"
        np.testing.assert_array_equal(got, ref_logits)
        for a, b in zip(state_to_host(srv.docs["d0"].state), ref_state):
            np.testing.assert_array_equal(a, b)
    # spill files are removed on rehydration
    assert os.listdir(str(tmp_path)) == []


def test_state_host_roundtrip_helpers(setup):
    cfg, params = setup
    srv = _server(cfg, params)
    _open_docs(srv, cfg, 1, seed=2)
    state = srv.docs["d0"].state
    host = state_to_host(state)
    back = state_from_host(host)
    for a, b in zip(state, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert state_nbytes(host) == state_nbytes(state)


def test_budget_evicts_lru_and_pins_hold(setup):
    """A device budget of ~2 documents: opening a third evicts the least-
    recently-touched; a pinned document survives; pinning everything makes
    the next admission fail loudly."""
    cfg, params = setup
    srv = _server(cfg, params)
    _open_docs(srv, cfg, 1)
    per = _doc_bytes(srv)

    srv2 = _server(cfg, params, device_budget_bytes=int(2.4 * per),
                   max_batch=1)
    _open_docs(srv2, cfg, 2, seed=4)
    assert srv2.stats.evictions == 0
    srv2.pin("d1")
    srv2.open_document("d2", list(np.arange(DOC_LEN) % cfg.vocab))
    # d0 (LRU, unpinned) was evicted; pinned d1 stayed hot
    assert srv2.tier("d0") == "warm"
    assert srv2.tier("d1") == "hot" and srv2.tier("d2") == "hot"
    _reconcile(srv2)
    srv2.pin("d2")
    with pytest.raises(DeviceBudgetError):
        srv2.open_document("d3", list(np.arange(DOC_LEN) % cfg.vocab))
    assert "d3" not in srv2.docs
    srv2.unpin("d1")
    srv2.open_document("d3", list(np.arange(DOC_LEN) % cfg.vocab))
    assert srv2.tier("d1") == "warm" and srv2.tier("d3") == "hot"
    _reconcile(srv2)
    # edits on the evicted docs rehydrate transparently and stay correct
    srv2.submit_replace("d0", 0, 1)
    srv2.submit_replace("d1", 0, 1)
    srv2.flush()
    assert srv2.stats.rehydrations >= 2
    # the budget cannot hold d1 beside d0 in flight: d0 was adopted first
    assert srv2.stats.overlapped_dispatches == 0
    _reconcile(srv2)


def test_suggest_cache_is_soft_state(setup):
    """Decode caches are dropped before any document state is evicted, and
    a dropped cache changes nothing token-level."""
    cfg, params = setup
    srv = _server(cfg, params)
    _open_docs(srv, cfg, 1, seed=5)
    want = srv.suggest("d0", 4)
    assert srv.suggester.cache_nbytes("d0") > 0
    srv.store._drop_suggest("d0")
    assert srv.suggester.cache_nbytes("d0") == 0
    assert srv.stats.bytes_suggest == 0
    srv.docs["d0"].suggest_fresh = False  # force a refresh without the cache
    got = srv.suggest("d0", 4)
    np.testing.assert_array_equal(got, want)
    _reconcile(srv)


def test_failed_dispatch_on_evicted_doc_rolls_back_to_void(setup):
    """The rollback corner: a doc enters a take evicted, the take's grow
    re-ingest consumes its warm copy, and then the dispatch fails. Rollback
    must not raise (other docs in the round depend on it finishing) and
    must not lose the doc: it lands in the 'void' residency state and the
    next touch rebuilds it from the restored mirrors — final tokens and
    logits bitwise-match a server that never failed."""
    cfg, params = setup
    toks = list(np.arange(N_CAP) % cfg.vocab)  # fills n_cap: insert => grow

    oracle = _server(cfg, params)
    oracle.open_document("d", list(toks))
    oracle.submit_insert("d", 0, 3)
    oracle.flush()

    srv = _server(cfg, params)
    srv.open_document("d", list(toks))
    srv.evict("d", "warm")
    srv.submit_insert("d", 0, 3)
    eng = srv.engine(srv.C, srv.docs["d"].row_capacity)
    orig = eng.batch_apply_inserts
    eng.batch_apply_inserts = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected dispatch failure"))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            srv.step()
    finally:
        eng.batch_apply_inserts = orig
    # rolled back: mirrors pre-take, edit still queued, residency = void
    assert srv.tier("d") == "void" and srv.docs["d"].state is None
    assert list(srv.docs["d"].pending) == [("insert", 0, 3)]
    np.testing.assert_array_equal(srv.docs["d"].seq_tokens(), toks)
    # next touch rebuilds from the restored mirrors (full forward)...
    assert srv.store.ensure_hot(srv.docs["d"]) is not None
    assert srv.tier("d") == "hot" and srv.stats.rollback_rebuilds == 1
    srv.flush()  # ...and the still-queued edit applies normally
    np.testing.assert_array_equal(srv.tokens("d"), oracle.tokens("d"))
    np.testing.assert_array_equal(srv.logits("d"), oracle.logits("d"))
    _reconcile(srv)


# ------------------------------------------------------ differential churn


def test_tiered_churn_matches_unbounded_oracle(setup, tmp_path):
    """The acceptance harness: a mixed edit+suggest stream over more
    documents than the device budget admits — with forced warm AND cold
    evictions interleaved between edits — produces logits bit-identical and
    suggestions token-identical to an unbounded-budget oracle server, and
    closing every document leaks nothing."""
    cfg, params = setup
    probe = _server(cfg, params)
    _open_docs(probe, cfg, 1)
    per = _doc_bytes(probe)

    spill = str(tmp_path / "spill")
    srv = _server(cfg, params, device_budget_bytes=int(2.6 * per),
                  host_budget_bytes=int(1.2 * per), spill_dir=spill)
    oracle = _server(cfg, params)  # unbounded: everything stays hot
    n_docs = 4
    _open_docs(srv, cfg, n_docs, seed=6)
    refs = _open_docs(oracle, cfg, n_docs, seed=6)
    refs = {d: list(t) for d, t in refs.items()}
    assert srv.stats.evictions > 0, "budget must force evictions at open"

    rng = np.random.default_rng(9)
    forced = ["warm", "cold"]
    for t in range(10):
        did = f"d{int(rng.integers(n_docs))}"
        n = len(refs[did])
        op = ["replace", "insert", "delete"][int(rng.integers(3))]
        if op == "delete" and n <= 2:
            op = "replace"
        if op == "replace":
            pos, tok = int(rng.integers(n)), int(rng.integers(cfg.vocab))
            srv.submit_replace(did, pos, tok)
            oracle.submit_replace(did, pos, tok)
            refs[did][pos] = tok
        elif op == "insert":
            pos, tok = int(rng.integers(n + 1)), int(rng.integers(cfg.vocab))
            srv.submit_insert(did, pos, tok)
            oracle.submit_insert(did, pos, tok)
            refs[did].insert(pos, tok)
        else:
            pos = int(rng.integers(n))
            srv.submit_delete(did, pos)
            oracle.submit_delete(did, pos)
            del refs[did][pos]
        # force extra churn: demote some OTHER unpinned doc between edits
        victim = f"d{(int(did[1:]) + 1 + t % (n_docs - 1)) % n_docs}"
        if srv.tier(victim) == "hot":
            srv.evict(victim, forced[t % 2])
        srv.flush()
        oracle.flush()
        np.testing.assert_array_equal(srv.tokens(did), refs[did])
        np.testing.assert_array_equal(srv.logits(did), oracle.logits(did))
        if t % 3 == 0:
            s_t = srv.suggest(did, 4)
            s_o = oracle.suggest(did, 4)
            np.testing.assert_array_equal(s_t, s_o)
        _reconcile(srv)

    st = srv.stats
    assert st.evictions > 0 and st.spills > 0 and st.rehydrations > 0
    assert st.hot_hit_rate < 1.0
    assert oracle.stats.evictions == oracle.stats.rehydrations == 0
    # final sweep: every document bit-identical to the oracle
    for did in refs:
        np.testing.assert_array_equal(srv.tokens(did), refs[did])
        np.testing.assert_array_equal(srv.logits(did), oracle.logits(did))
    # teardown leaks nothing: no bytes, no spill files, no caches
    for did in list(srv.docs):
        srv.close_document(did)
    assert len(srv.docs) == 0
    assert st.bytes_hot == st.bytes_warm == st.bytes_cold == 0
    assert st.bytes_suggest == 0
    assert srv._sugg is None or srv._sugg.cached_keys() == []
    assert not os.path.isdir(spill) or os.listdir(spill) == []
