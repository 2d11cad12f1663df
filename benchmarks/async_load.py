"""Concurrent-load benchmark for the deadline-batching async front end
(ISSUE 6 tentpole).

Client threads (one per document) drive ``AsyncBatchServer`` under two
traffic shapes:

* ``burst``       — each client submits its whole edit burst, then asks for
  one suggestion: the deadline batcher's best case (bursts coalesce into
  few dispatch rounds);
* ``interactive`` — each client alternates single edit -> blocking
  suggestion: the latency-bound worst case (every round is small, the
  per-request SLO dominates).

Both shapes are compared token-exactly against a sequential replay of the
same per-document request streams on the same ``BatchServer`` — the gated
bits (``tokens_match``, ``suggestions_match``, ``edits_applied``) are
deterministic because threads own disjoint documents and each document's
stream comes from the seeded ``TrafficGenerator`` in ``data/edit_stream``
(shared with ``benchmarks.fleet_load``). Latency percentiles (admission-to-completion, from
``BatchStats.edit_latency`` / ``suggest_latency``), throughput and round
accounting are reported but never gated (runner noise).

Timing protocol: a warmup pass runs the identical workload on scratch
documents first (compiles every dispatch/refresh shape), then the latency
histograms are reset and the timed pass runs on fresh documents — the same
discipline as ``benchmarks.suggest_reuse``.

Emits ``results/BENCH_async_load.json`` plus name,value CSV lines.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

from benchmarks.common import ensure_results


def _submit(server, doc_id: str, op) -> object:
    kind, pos, tok = op
    if kind == "insert":
        return server.submit_insert(doc_id, pos, tok)
    if kind == "delete":
        return server.submit_delete(doc_id, pos)
    return server.submit_replace(doc_id, pos, tok)


def run(n_docs: int = 3, doc_len: int = 24, n_edits: int = 6,
        n_new: int = 4, seed: int = 0, max_batch_delay_ms: float = 5.0,
        warmup: bool = True) -> list[dict]:
    import jax

    from repro.common.compile_cache import enable_compilation_cache
    from repro.configs.vq_opt_125m import smoke_config
    from repro.data.edit_stream import TrafficGenerator
    from repro.models import transformer as T
    from repro.serving.async_server import AsyncBatchServer
    from repro.serving.batch_server import BatchServer
    from repro.serving.latency import LatencyStats

    enable_compilation_cache()
    cfg = smoke_config(vqt=True)
    params = jax.device_get(T.init_params(jax.random.PRNGKey(seed), cfg))
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=32,
                      max_batch=max(n_docs, 2), min_doc_capacity=16)

    records = []
    traffic = TrafficGenerator(vocab=cfg.vocab, n_docs=n_docs,
                               doc_len=doc_len, seed=seed)
    for scenario in ("burst", "interactive"):
        # identical seeded streams for warmup / timed / oracle replays
        def make_docs(tag):
            docs = {}
            for i in range(n_docs):
                ref = traffic.base_document(i)
                ops = traffic.session_ops(i, n_edits, list(ref))
                docs[f"{scenario}_{tag}_{i}"] = (ref, ops)
            return docs

        def drive(asrv, doc_id, ops, out):
            if scenario == "burst":
                for op in ops:
                    _submit(asrv, doc_id, op)
                out.append(asrv.suggest(doc_id, n_new).result(600))
            else:  # interactive: edit -> blocking suggestion, per keystroke
                for op in ops:
                    _submit(asrv, doc_id, op)
                    out.append(asrv.suggest(doc_id, n_new).result(600))

        phases = (("warm", False),) if warmup else ()
        phases += (("timed", True),)
        for tag, timed in phases:
            docs = make_docs(tag)
            if timed:
                # fresh histograms: warmup latencies include jit compiles
                srv.stats.edit_latency = LatencyStats()
                srv.stats.suggest_latency = LatencyStats()
            suggestions = {d: [] for d in docs}
            t0 = time.perf_counter()
            with AsyncBatchServer(
                    srv, max_batch_delay_ms=max_batch_delay_ms) as asrv:
                for t in [asrv.open_document(d, ref)
                          for d, (ref, _) in docs.items()]:
                    t.result(600)
                threads = [threading.Thread(
                    target=drive, args=(asrv, d, ops, suggestions[d]))
                    for d, (_, ops) in docs.items()]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                final = {d: asrv.tokens(d).result(600) for d in docs}
                astats = asrv.stats
            wall_s = time.perf_counter() - t0
            if not timed:
                for d in docs:
                    srv.close_document(d)
                continue

            # sequential oracle: same per-document streams, same server
            tokens_match = True
            suggestions_match = True
            for d, (ref, ops) in docs.items():
                oid = f"{d}_oracle"
                srv.open_document(oid, ref)
                want_sugg = []
                if scenario == "burst":
                    for op in ops:
                        _submit(srv, oid, op)
                    want_sugg.append(srv.suggest(oid, n_new))
                else:
                    for op in ops:
                        _submit(srv, oid, op)
                        want_sugg.append(srv.suggest(oid, n_new))
                tokens_match &= bool(
                    np.array_equal(final[d], srv.tokens(oid)))
                suggestions_match &= len(want_sugg) == len(suggestions[d])
                suggestions_match &= all(
                    np.array_equal(g, w)
                    for g, w in zip(suggestions[d], want_sugg))
                srv.close_document(oid)
            for d in docs:
                srv.close_document(d)

            total_edits = n_docs * n_edits
            el, sl = srv.stats.edit_latency, srv.stats.suggest_latency
            rec = {
                "scenario": scenario,
                "n_docs": n_docs,
                "doc_len": doc_len,
                "n_edits": n_edits,
                "n_new": n_new,
                "max_batch_delay_ms": max_batch_delay_ms,
                "tokens_match": tokens_match,
                "suggestions_match": suggestions_match,
                "edits_applied": astats.admitted_edits,
                "suggests_served": astats.admitted_suggests,
                "rounds": astats.rounds,
                "deadline_rounds": astats.deadline_rounds,
                "full_rounds": astats.full_rounds,
                "mean_edits_per_round": astats.mean_edits_per_round,
                "requests_failed": astats.requests_failed,
                # wall-clock: reported, never gated
                "wall_s": wall_s,
                "edits_per_s": total_edits / max(wall_s, 1e-9),
                "edit_latency": el.summary(),
                "suggest_latency": sl.summary(),
            }
            records.append(rec)
            print(f"async_load,{scenario},edits_per_s,"
                  f"{rec['edits_per_s']:.1f}")
            print(f"async_load,{scenario},edit_p99_ms,"
                  f"{el.p99:.1f}")
            print(f"async_load,{scenario},suggest_p99_ms,"
                  f"{sl.p99:.1f}")
            print(f"async_load,{scenario},mean_edits_per_round,"
                  f"{rec['mean_edits_per_round']:.2f}")

    out = os.path.join(ensure_results(), "BENCH_async_load.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=2)
    print(f"async_load,written,{out}")
    return records


if __name__ == "__main__":
    run()
