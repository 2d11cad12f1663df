"""Batched jit serving == per-document serving == the NumPy engine.

Parity ladder (ISSUE 1 tentpole): every slice of a batched result must match
the single-document jit engine, which in turn matches the host NumPy
``IncrementalEngine`` (identical codes, float-tolerance activations) — and
the overflow → full-forward fallback must restore exactness.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vq_opt_125m import smoke_config
from repro.core.incremental import IncrementalEngine
from repro.models import transformer as T
from repro.serving.batch_engine import (
    BatchedJitEngine, stack_states, unstack_state,
)
from repro.serving.batch_server import BatchServer, next_pow2
from repro.serving.jit_engine import JitIncrementalEngine


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(vqt=True)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    beng = BatchedJitEngine(params, cfg, edit_capacity=4, row_capacity=32)
    neng = IncrementalEngine(jax.device_get(params), cfg)
    return cfg, params, beng, neng


def _batch_docs(cfg, b=3, n=40, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab, (b, n))
    positions = np.tile(np.arange(n) * 5, (b, 1))
    return tokens, positions


def _assert_doc_matches_numpy(js, ns, neng, atol=3e-4):
    for li in range(len(neng.layers)):
        np.testing.assert_array_equal(np.asarray(js.codes[li]),
                                      ns.layers[li].codes)
    np.testing.assert_allclose(np.asarray(js.x[-1]), ns.xs[-1], atol=atol)


def test_batch_full_forward_matches_numpy_per_doc(setup):
    cfg, params, beng, neng = setup
    tokens, positions = _batch_docs(cfg)
    bstate = beng.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    for b in range(tokens.shape[0]):
        ns = neng.full_forward(tokens[b], positions[b])
        _assert_doc_matches_numpy(unstack_state(bstate, b), ns, neng)


def test_batch_apply_replaces_matches_numpy_per_doc(setup):
    cfg, params, beng, neng = setup
    # a row bucket as wide as the 40-token documents: no propagation can
    # overflow it, so every slice is the incremental result itself
    beng = BatchedJitEngine({}, cfg, edit_capacity=4, row_capacity=64,
                            _weights=beng.weights)
    tokens, positions = _batch_docs(cfg, seed=1)
    bstate = beng.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    nstates = [neng.full_forward(tokens[b], positions[b]) for b in range(3)]
    rng = np.random.default_rng(2)
    for trial in range(2):
        # disjoint per-doc edit buckets, including one all-empty bucket
        edit_pos = np.full((3, 4), -1, np.int32)
        edit_tok = np.zeros((3, 4), np.int32)
        per_doc = []
        for b in range(2):  # doc 2 gets no edits this round
            pos = sorted(rng.choice(tokens.shape[1], 2, replace=False))
            tok = rng.integers(0, cfg.vocab, 2)
            edit_pos[b, :2] = pos
            edit_tok[b, :2] = tok
            per_doc.append((list(map(int, pos)), list(map(int, tok))))
        bstate, overflow = beng.batch_apply_replaces(
            bstate, jnp.asarray(edit_pos), jnp.asarray(edit_tok))
        assert not np.asarray(overflow).any()
        for b, (pos, tok) in enumerate(per_doc):
            nstates[b] = neng.apply_replaces(nstates[b], pos, tok)
        for b in range(3):
            _assert_doc_matches_numpy(unstack_state(bstate, b), nstates[b], neng)


def test_batch_matches_single_doc_engine_exactly(setup):
    # float atol is 3e-4, not 1e-5: the vmapped and single-doc programs
    # batch their reductions differently, and the drift depends on the CPU
    # client's partitioning (the forced-host-device CI leg reaches ~2.4e-4).
    # Codes — the quantity serving correctness rests on — must match exactly.
    cfg, params, beng, neng = setup
    seng = JitIncrementalEngine({}, cfg, edit_capacity=4, row_capacity=32,
                                _weights=beng.weights)
    tokens, positions = _batch_docs(cfg, seed=3)
    bstate = beng.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    singles = [seng.full_forward(jnp.asarray(tokens[b]), jnp.asarray(positions[b]))
               for b in range(3)]
    restacked = stack_states(singles)
    for a, c in zip(bstate, restacked):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=3e-4)
    np.testing.assert_array_equal(np.asarray(bstate.codes),
                                  np.asarray(restacked.codes))
    ep = jnp.asarray([[1, 20, -1, -1]] * 3, jnp.int32)
    et = jnp.asarray([[7, 9, 0, 0]] * 3, jnp.int32)
    b2, ovf = beng.batch_apply_replaces(bstate, ep, et)
    s2, o2 = seng.apply_replaces(singles[0], ep[0], et[0])
    assert bool(ovf[0]) == bool(o2)
    np.testing.assert_allclose(np.asarray(unstack_state(b2, 0).x),
                               np.asarray(s2.x), atol=3e-4)
    np.testing.assert_array_equal(np.asarray(unstack_state(b2, 0).codes),
                                  np.asarray(s2.codes))


def test_batch_per_doc_overflow_flags(setup):
    """Overflow is per-document: a wide edit trips only its own flag."""
    cfg, params, beng, neng = setup
    tight = BatchedJitEngine({}, cfg, edit_capacity=4, row_capacity=2,
                             _weights=beng.weights)
    tokens, positions = _batch_docs(cfg, seed=4)
    bstate = tight.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    edit_pos = np.full((3, 4), -1, np.int32)
    edit_tok = np.zeros((3, 4), np.int32)
    edit_pos[1] = [1, 2, 3, 4]  # 4 edits alone exceed R=2 for doc 1 only
    edit_tok[1] = [9, 9, 9, 9]
    _, overflow = tight.batch_apply_replaces(
        bstate, jnp.asarray(edit_pos), jnp.asarray(edit_tok))
    overflow = np.asarray(overflow)
    assert bool(overflow[1])
    assert not bool(overflow[0]) and not bool(overflow[2])


def test_batched_patch_kernel_route_matches_einsum(setup):
    """use_patch_kernel=True routes the column patch through the Pallas
    kernel (batch grid dimension under vmap) — results must be identical."""
    cfg, params, beng, neng = setup
    keng = BatchedJitEngine({}, cfg, edit_capacity=4, row_capacity=32,
                            use_patch_kernel=True, _weights=beng.weights)
    tokens, positions = _batch_docs(cfg, b=2, n=40, seed=5)
    bstate = beng.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    ep = jnp.asarray([[2, 11, -1, -1], [5, -1, -1, -1]], jnp.int32)
    et = jnp.asarray([[3, 4, 0, 0], [8, 0, 0, 0]], jnp.int32)
    s_e, o_e = beng.batch_apply_replaces(bstate, ep, et)
    s_k, o_k = keng.batch_apply_replaces(bstate, ep, et)
    np.testing.assert_array_equal(np.asarray(o_e), np.asarray(o_k))
    np.testing.assert_array_equal(np.asarray(s_e.codes), np.asarray(s_k.codes))
    np.testing.assert_allclose(np.asarray(s_e.x), np.asarray(s_k.x), atol=2e-5)


# --------------------------------------------------------------- BatchServer


def test_server_parity_with_numpy_engine(setup):
    """End-to-end: padded, bucketed, batch-dispatched documents match the
    NumPy engine run on the same padded inputs."""
    cfg, params, beng, neng = setup
    srv = BatchServer(jax.device_get(params), cfg, edit_capacity=4,
                      row_capacity=16, max_batch=4, min_doc_capacity=16)
    rng = np.random.default_rng(6)
    ref = {}
    for i in range(4):
        n = int(rng.integers(18, 40))
        toks = rng.integers(0, cfg.vocab, n)
        ref[f"d{i}"] = list(toks)
        srv.open_document(f"d{i}", toks)
    for _ in range(25):
        did = f"d{int(rng.integers(4))}"
        pos = int(rng.integers(len(ref[did])))
        tok = int(rng.integers(cfg.vocab))
        srv.submit_replace(did, pos, tok)
        ref[did][pos] = tok
    srv.flush()
    assert srv.pending_count() == 0
    assert srv.stats.edits_applied == srv.stats.edits_submitted == 25
    for did, toks in ref.items():
        assert list(srv.tokens(did)) == toks
        doc = srv.docs[did]
        ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
        js = doc.state
        sl = np.asarray(doc.slots)
        for li in range(len(neng.layers)):
            np.testing.assert_array_equal(np.asarray(js.codes[li])[sl],
                                          ns.layers[li].codes)
        np.testing.assert_allclose(np.asarray(js.x[-1])[sl],
                                   ns.xs[-1], atol=3e-4)


def test_server_overflow_fallback_restores_exactness(setup):
    """R=1 guarantees overflow on nearly every edit; the full-forward
    fallback + capacity doubling must keep the state exact anyway."""
    cfg, params, beng, neng = setup
    srv = BatchServer(jax.device_get(params), cfg, edit_capacity=4,
                      row_capacity=1, max_batch=4, min_doc_capacity=16)
    rng = np.random.default_rng(7)
    toks = list(rng.integers(0, cfg.vocab, 30))
    srv.open_document("d", toks)
    for pos in (3, 9, 15):
        tok = int(rng.integers(cfg.vocab))
        srv.submit_replace("d", pos, tok)
        toks[pos] = tok
    srv.flush()
    assert srv.stats.overflows >= 1
    assert srv.stats.full_forwards >= 2  # ingest + at least one fallback
    doc = srv.docs["d"]
    assert list(srv.tokens("d")) == toks
    ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
    sl = np.asarray(doc.slots)
    np.testing.assert_allclose(np.asarray(doc.state.x[-1])[sl],
                               ns.xs[-1], atol=3e-4)
    # capacity doubling: the doc's row bucket grew, still a power of two
    assert doc.row_capacity > 1
    assert doc.row_capacity & (doc.row_capacity - 1) == 0


def test_server_logits_match_numpy(setup):
    cfg, params, beng, neng = setup
    srv = BatchServer(jax.device_get(params), cfg, edit_capacity=4,
                      row_capacity=16, min_doc_capacity=16)
    rng = np.random.default_rng(8)
    toks = rng.integers(0, cfg.vocab, 20)
    srv.open_document("d", toks)
    srv.submit_replace("d", 4, 7)
    # unflushed edits: every read accessor must refuse stale state
    for accessor in (srv.logits, srv.state, srv.tokens):
        with pytest.raises(RuntimeError):
            accessor("d")
    srv.flush()
    doc = srv.docs["d"]
    got = srv.logits("d")
    assert got.shape == (cfg.vocab,)
    # recompute from the real-length, sequence-ordered document directly
    ns_real = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
    np.testing.assert_allclose(got, neng.logits_at(ns_real), atol=3e-4)


def test_grow_reingest_does_not_race_host_mirrors(setup):
    """Regression: jax reads numpy inputs ASYNCHRONOUSLY (and may zero-copy
    them), so a grow-triggered re-ingest that handed the live host mirrors
    to ``full_forward`` could "see" the inserts the very same take peeled
    AFTER it — the dispatch then applied them a second time (double-counted
    ``n_real``, garbage T columns, VQ code flips). ``_device_copy`` now
    snapshots mirrors eagerly. This drives the exact traffic shape that
    exposed the race — full-capacity documents whose insert takes grow +
    re-ingest while other documents keep the device queue busy — and
    asserts codes/counters stay exact against the NumPy engine."""
    cfg, params, beng, neng = setup
    srv = BatchServer(jax.device_get(params), cfg, edit_capacity=4,
                      row_capacity=64, max_batch=8, min_doc_capacity=64)
    rng = np.random.default_rng(0)
    ref = {f"d{i}": list(rng.integers(0, cfg.vocab, 64)) for i in range(8)}
    srv.open_documents({d: list(t) for d, t in ref.items()})
    for _ in range(24):  # mixed stream; docs are FULL, so inserts grow
        did = f"d{int(rng.integers(8))}"
        r = ref[did]
        kind = rng.choice(["replace", "insert", "delete"], p=[0.5, 0.3, 0.2])
        if kind == "insert":
            p, t = int(rng.integers(len(r) + 1)), int(rng.integers(cfg.vocab))
            srv.submit_insert(did, p, t)
            r.insert(p, t)
        elif kind == "delete" and len(r) > 1:
            p = int(rng.integers(len(r)))
            srv.submit_delete(did, p)
            del r[p]
        else:
            p, t = int(rng.integers(len(r))), int(rng.integers(cfg.vocab))
            srv.submit_replace(did, p, t)
            r[p] = t
    srv.flush()
    assert srv.stats.grows >= 1  # the race's trigger really fired
    for did, r in ref.items():
        assert list(srv.tokens(did)) == r, did
        doc = srv.docs[did]
        assert int(doc.state.n_real) == int(doc.valid.sum()) == len(r)
        ns = neng.full_forward(doc.seq_tokens(), doc.seq_positions())
        sl = np.asarray(doc.slots)
        for li in range(len(neng.layers)):
            np.testing.assert_array_equal(np.asarray(doc.state.codes[li])[sl],
                                          ns.layers[li].codes)
        np.testing.assert_allclose(srv.logits(did), neng.logits_at(ns),
                                   atol=3e-4)


def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 17, 64)] == [1, 2, 4, 32, 64]
    assert next_pow2(3, minimum=16) == 16


def test_weights_are_arguments_not_constants(setup):
    """No compiled serving program embeds a weight: the lowered batched edit
    step takes every weight it reads as an argument, and holds no constant
    the size of a weight."""
    import re

    cfg, params, beng, neng = setup
    tokens, positions = _batch_docs(cfg, b=2)
    state = beng.batch_full_forward(jnp.asarray(tokens), jnp.asarray(positions))
    bucket = jnp.full((2, 4), -1, jnp.int32)
    text = beng._batch_apply_edits_local.jitted.lower(
        beng, beng.wts, state, bucket, bucket, bucket, bucket).as_text()
    main = re.search(r"func\.func public @main\((.*?)\)\s*->", text, re.S)
    signature = main.group(1)
    for name in ("wq", "w_up", "w_down", "cb_per_head", "c_wo"):
        dims = "x".join(map(str, beng.wts["W"][name].shape))
        assert f"tensor<{dims}xf32>" in signature, name
    for name in ("tok_emb", "pos_emb"):
        dims = "x".join(map(str, beng.wts["extras"][name].shape))
        assert f"tensor<{dims}xf32>" in signature, name
    for dims in re.findall(r"stablehlo\.constant dense<[^>]*> : "
                           r"tensor<([0-9x]+)x[a-z0-9]+>", text):
        assert np.prod([int(x) for x in dims.split("x")]) < 1024, dims
