"""Edit-mix benchmark: replace-only vs mixed (insert/delete-heavy) streams.

Two views of the same workload, so the perf trajectory of the full edit
algebra (ISSUE 2 tentpole) is tracked from this PR on:

* **ops** — the paper's metric, metered by the NumPy ``IncrementalServer``:
  incremental ops vs the dense recompute-from-scratch equivalent;
* **wall-clock** — the deployment metric: total ``BatchServer.flush`` time
  (typed fixed-shape dispatches, including any defrag/grow/overflow
  re-ingests) per edit, plus the traced-shape count, which must stay
  bounded by the capacity grid rather than grow with traffic.

Timing discipline (ISSUE 7): the measured segment is bracketed by
``jax.block_until_ready`` on every resident document state, so async
dispatch cannot leak device work across the timer; and the warmup is a
REPLAY — the same seeded edit trace is pre-generated once and applied to
warmup twins (``w*``) of the measured documents (``d*``) first, so every
compiled shape the measured pass needs is warm, deterministically, before
the clock starts. The mixed/replace-only wall-clock ratio is CI-gated
(``check_regression``): structural streams must stay within a small factor
of the replace-only fast path now that grow/defrag run on-device and
capacity classes collapse the shape lattice.

Emits ``results/BENCH_edit_mix.json`` (machine-readable, one record per
workload) and prints name,value CSV lines like the other benchmarks.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmarks.common import dense_ops_for, ensure_results

MIXES = {
    "replace_only": {"replace": 1.0, "insert": 0.0, "delete": 0.0},
    # the paper's atomic-edit workload is structural-edit heavy (typing
    # inserts + corrections); 40% inserts/deletes comfortably exceeds the
    # >=30% acceptance bar
    "mixed": {"replace": 0.6, "insert": 0.25, "delete": 0.15},
}

# BatchServer knobs for the legacy (pre-fused) serving stack — the A/B
# reference for the fused ragged hot path. `run(legacy=True)` measures it
# under the SAME sync + warmup-replay discipline.
LEGACY_FLAGS = dict(use_fused_kernel=False, capacity_class_step=2,
                    device_grow=False, device_defrag=False)


def _stream(rng, ref: list, vocab: int, mix: dict, n_edits: int):
    """Yield (op, pos, tok) against a live reference list."""
    ops, ps = list(mix), np.asarray([mix[k] for k in mix])
    for _ in range(n_edits):
        op = str(rng.choice(ops, p=ps / ps.sum()))
        if op == "delete" and len(ref) <= 1:
            op = "replace"
        if op == "replace":
            pos, tok = int(rng.integers(len(ref))), int(rng.integers(vocab))
            ref[pos] = tok
        elif op == "insert":
            pos, tok = int(rng.integers(len(ref) + 1)), int(rng.integers(vocab))
            ref.insert(pos, tok)
        else:
            pos, tok = int(rng.integers(len(ref))), 0
            del ref[pos]
        yield op, pos, tok


def _make_trace(rng, refs: dict, vocab: int, mix: dict,
                n_edits: int) -> list:
    """Pre-generate the full deterministic edit trace: [(doc, op, pos, tok)].
    ``refs`` is mutated to the post-trace document contents."""
    doc_ids = sorted(refs)
    trace = []
    for _ in range(n_edits):
        did = doc_ids[int(rng.integers(len(doc_ids)))]
        for op, pos, tok in _stream(rng, refs[did], vocab, mix, 1):
            trace.append((did, op, pos, tok))
    return trace


def _sync(srv) -> None:
    """Barrier every resident device state (timed-segment boundary)."""
    import jax

    for doc in srv.docs.values():
        if doc.state is not None:
            jax.block_until_ready(doc.state)


def run(doc_len: int = 192, n_edits: int = 24, n_docs: int = 4,
        seed: int = 0, legacy: bool = False) -> list[dict]:
    import jax

    from repro.configs.vq_opt_125m import smoke_config
    from repro.core.edits import Edit
    from repro.models import transformer as T
    from repro.serving.batch_server import BatchServer
    from repro.serving.engine import IncrementalServer

    cfg = smoke_config(vqt=True)
    params = jax.device_get(T.init_params(jax.random.PRNGKey(seed), cfg))
    flags = LEGACY_FLAGS if legacy else {}
    records = []
    for name, mix in MIXES.items():
        rng = np.random.default_rng(seed)
        base_docs = {f"d{i}": list(rng.integers(0, cfg.vocab, doc_len))
                     for i in range(n_docs)}

        # ---- op view (single-worker NumPy server, the paper's metric)
        op_srv = IncrementalServer(params, cfg)
        ops = dense = 0
        doc_id = "d0"
        ref = list(base_docs[doc_id])
        op_srv.open_document(doc_id, ref)
        for op, pos, tok in _stream(rng, ref, cfg.vocab, mix, n_edits):
            ops += op_srv.apply_edit(doc_id, Edit(op, pos, tok))
            dense += dense_ops_for(cfg, len(ref))

        # ---- wall-clock view (batched jit server, typed buckets)
        # warmup twins w* carry the IDENTICAL trace first: same initial
        # content, same seed, same edits -> the same (B, n_cap, C, R)
        # dispatch sequence, so the measured pass re-traces nothing
        srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=64,
                          max_batch=n_docs, min_doc_capacity=64, **flags)
        srv.open_documents({f"w{i}": list(v) for i, (_, v) in
                            enumerate(sorted(base_docs.items()))})
        srv.open_documents(base_docs)
        refs = {k: list(v) for k, v in base_docs.items()}
        trace = _make_trace(np.random.default_rng(seed + 1), refs,
                            cfg.vocab, mix, n_edits)
        for did, op, pos, tok in trace:  # warmup replay on the twins
            srv.submit_edit("w" + did[1:], Edit(op, pos, tok))
            srv.flush()
        _sync(srv)
        warm_shapes = srv.stats.traced_shapes
        t0 = time.perf_counter()
        for did, op, pos, tok in trace:  # measured pass, same trace
            srv.submit_edit(did, Edit(op, pos, tok))
            srv.flush()
        _sync(srv)
        wall = time.perf_counter() - t0
        for did, r in refs.items():
            assert list(srv.tokens(did)) == r, did

        structural = 1.0 - mix["replace"]
        rec = {
            "workload": name,
            "structural_fraction": round(structural, 3),
            "doc_len": doc_len,
            "n_edits": n_edits,
            "legacy_stack": bool(legacy),
            "ops_incremental": int(ops),
            "ops_dense_equiv": int(dense),
            "ops_speedup": round(dense / max(ops, 1), 2),
            "wall_s_per_edit": round(wall / n_edits, 5),
            "batch_dispatches": srv.stats.batch_steps,
            "traced_shapes": srv.stats.traced_shapes,
            "measured_pass_new_shapes":
                srv.stats.traced_shapes - warm_shapes,
            "overflows": srv.stats.overflows,
            "defrags": srv.stats.defrags,
            "device_defrags": srv.stats.device_defrags,
            "grows": srv.stats.grows,
            "device_grows": srv.stats.device_grows,
        }
        records.append(rec)
        print(f"edit_mix,{name},ops_speedup={rec['ops_speedup']},"
              f"wall_per_edit_ms={rec['wall_s_per_edit']*1e3:.2f},"
              f"traced_shapes={rec['traced_shapes']}")
    # the CI-gated fusion metric: how much slower a structural stream is
    # than the replace-only fast path, warm, on the same server config
    by_name = {r["workload"]: r for r in records}
    ratio = (by_name["mixed"]["wall_s_per_edit"]
             / max(by_name["replace_only"]["wall_s_per_edit"], 1e-9))
    by_name["mixed"]["wall_ratio_mixed_vs_replace"] = round(ratio, 3)
    print(f"edit_mix,wall_ratio_mixed_vs_replace,{ratio:.3f}")
    out = os.path.join(ensure_results(), "BENCH_edit_mix.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=2)
    print(f"wrote {out}")
    return records


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--legacy", action="store_true",
                    help="measure the pre-fused serving stack (A/B reference)")
    ap.add_argument("--doc-len", type=int, default=192)
    ap.add_argument("--n-edits", type=int, default=24)
    args = ap.parse_args()
    run(doc_len=args.doc_len, n_edits=args.n_edits, legacy=args.legacy)
