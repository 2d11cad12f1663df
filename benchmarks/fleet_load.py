"""Fleet serving load test: router + replica workers under seeded traffic,
with a forced cross-replica migration and a forced failover mid-run
(ISSUE 10 tentpole).

A ``TrafficGenerator`` schedule (zipf document popularity, Poisson-ish
session arrival/departure, typing vs revise bursts — shared with
``benchmarks.async_load``) drives a ``FleetRouter`` over N subprocess
replicas. Halfway through, one document is migrated to another replica
through the shared cold tier; at the three-quarter mark the fleet is
checkpointed and the busiest replica is hard-killed, so the remaining
events exercise failover-recovered documents on the survivors.

Exactness: the identical event schedule is replayed sequentially on a
single in-process ``BatchServer`` built from the same seeded parameters
(the oracle). Every suggestion and every surviving document's final tokens
must be token-exact despite the migration and the kill — that is the
acceptance criterion of DESIGN.md §11, and ``tokens_exact`` /
``suggestions_exact`` / ``leak_free`` are gated ``must_equal True`` in
``benchmarks.check_regression``. ``migrations`` / ``failovers`` /
``edits_acked`` are deterministic counts (gated exactly); ``hot_hit_rate``
gets a small tolerance. Latency p99 and throughput are wall-clock — gated
only with deliberately cavernous tolerances that catch order-of-magnitude
serving regressions, not runner noise.

Timing protocol: per-replica pinned warmup documents pay the jit compiles,
then ``FleetRouter.reset_latency`` restarts the histograms before the
measured event drive.

Emits ``results/BENCH_fleet_load.json`` plus name,value CSV lines.
Default is the gated 2-replica CPU config; ``--full`` adds 1- and
4-replica sweeps.
"""
from __future__ import annotations

import argparse
import json
import os
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


def _cold_leftovers(cold_dir: str) -> list[str]:
    try:
        return sorted(f for f in os.listdir(cold_dir)
                      if f.endswith((".npz", ".lease")))
    except FileNotFoundError:
        return []


def run_fleet(n_replicas: int = 2, n_docs: int = 3, n_sessions: int = 5,
              doc_len: int = 24, n_new: int = 4, seed: int = 0,
              chaos: bool = True, max_batch_delay_ms: float = 5.0) -> dict:
    import tempfile

    import jax

    from repro.configs import get_config
    from repro.data.edit_stream import TrafficGenerator
    from repro.models import transformer as T
    from repro.serving.batch_server import BatchServer
    from repro.serving.fleet import FleetRouter

    cfg = get_config("vq-opt-125m", smoke=True)
    traffic = TrafficGenerator(vocab=cfg.vocab, n_docs=n_docs,
                               doc_len=doc_len, seed=seed)
    events, final_refs = traffic.fleet_events(n_sessions, n_new=n_new)
    n_edit_events = sum(1 for e in events if e[0] == "edit")
    chaos = chaos and n_replicas >= 2
    mig_at = len(events) // 2
    kill_at = (3 * len(events)) // 4

    cold_dir = tempfile.mkdtemp(prefix="repro-fleet-bench-")
    suggestions: list[tuple[str, np.ndarray]] = []
    open_docs: set[str] = set()
    pending: list = []
    edits_acked = 0
    migrations_forced = 0

    fleet = FleetRouter(n_replicas, cold_dir=cold_dir,
                        max_batch_delay_ms=max_batch_delay_ms, seed=seed)
    try:
        # warmup: one pinned document per replica compiles every dispatch
        # shape this schedule will touch (open/edit kinds/suggest/tokens)
        for r in range(n_replicas):
            wid = f"warm{r}"
            fleet.open_document(wid, traffic.base_document(0),
                                replica=r).result(600)
            for op in (("insert", 0, 7), ("replace", 1, 8), ("delete", 0, 0)):
                _submit(fleet, wid, op).result(600)
            fleet.suggest(wid, n_new).result(600)
            fleet.close_document(wid).result(600)
        fleet.reset_latency(600)

        t0 = time.perf_counter()
        for i, ev in enumerate(events):
            if chaos and i == mig_at and open_docs:
                # forced live migration: shared-cold-tier export/import
                doc = sorted(open_docs)[0]
                src = fleet.owner_of(doc)
                fleet.migrate(doc, (src + 1) % n_replicas)
                migrations_forced += 1
            if chaos and i == kill_at:
                # forced failover: everything acked, snapshot the fleet,
                # then hard-kill the busiest replica — survivors adopt its
                # documents from the shared snapshots
                for t in pending:
                    t.result(600)
                    edits_acked += 1
                pending.clear()
                fleet.checkpoint(600)
                counts: dict[int, int] = {}
                for d in sorted(open_docs):
                    o = fleet.owner_of(d)
                    counts[o] = counts.get(o, 0) + 1
                victim = (min(counts, key=lambda k: (-counts[k], k))
                          if counts else 0)
                fleet.kill_replica(victim)
            kind = ev[0]
            if kind == "open":
                fleet.open_document(ev[1], ev[2]).result(600)
                open_docs.add(ev[1])
            elif kind == "edit":
                pending.append(_submit(fleet, ev[1], ev[2]))
            elif kind == "suggest":
                suggestions.append((ev[1], fleet.suggest(ev[1],
                                                         ev[2]).result(600)))
            elif kind == "close":
                fleet.close_document(ev[1]).result(600)
                open_docs.discard(ev[1])
        for t in pending:
            t.result(600)
            edits_acked += 1
        pending.clear()
        wall_s = time.perf_counter() - t0

        final_fleet = {d: np.asarray(fleet.tokens(d).result(600))
                       for d in sorted(open_docs)}
        agg = fleet.stats(600)
    finally:
        fleet.close_fleet()

    leftovers = _cold_leftovers(cold_dir)
    procs_left = [r.idx for r in fleet.replicas if r.proc.poll() is None]
    leak_free = not leftovers and not procs_left

    # sequential oracle: identical schedule, one in-process server, same
    # seeded parameters as every replica (DESIGN.md §11 determinism contract)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    srv = BatchServer(params, cfg)
    oracle_sugg: list[tuple[str, np.ndarray]] = []
    for ev in events:
        if ev[0] == "open":
            srv.open_document(ev[1], ev[2])
        elif ev[0] == "edit":
            _submit(srv, ev[1], ev[2])
        elif ev[0] == "suggest":
            oracle_sugg.append((ev[1], np.asarray(srv.suggest(ev[1], ev[2]))))
        elif ev[0] == "close":
            srv.close_document(ev[1])
    tokens_exact = all(
        np.array_equal(final_fleet[d], srv.tokens(d))
        and np.array_equal(final_fleet[d], np.asarray(final_refs[d]))
        for d in final_fleet)
    suggestions_exact = (
        len(suggestions) == len(oracle_sugg)
        and all(da == db and np.array_equal(a, b)
                for (da, a), (db, b) in zip(suggestions, oracle_sugg)))

    router = agg["router"]
    rec = {
        "n_replicas": n_replicas,
        "n_docs": n_docs,
        "n_sessions": n_sessions,
        "doc_len": doc_len,
        "n_new": n_new,
        "seed": seed,
        "n_events": len(events),
        "n_edit_events": n_edit_events,
        "tokens_exact": bool(tokens_exact),
        "suggestions_exact": bool(suggestions_exact),
        "leak_free": bool(leak_free),
        "edits_acked": edits_acked,
        "migrations": router["migrations"],
        "failovers": router["failovers"],
        "failover_rehydrations": router["failover_rehydrations"],
        "failover_reopens": router["failover_reopens"],
        "repair_edits": router["repair_edits"],
        "hot_hit_rate": agg["hot_hit_rate"],
        "requests_failed": agg["requests_failed"],
        "rounds": agg["rounds"],
        "deadline_rounds": agg["deadline_rounds"],
        # wall-clock: reported; gated only with cavernous tolerances
        "wall_s": wall_s,
        "edits_per_s": n_edit_events / max(wall_s, 1e-9),
        "edit_p99_ms": agg["edit_latency"]["p99_ms"],
        "suggest_p99_ms": agg["suggest_latency"]["p99_ms"],
        "edit_latency": agg["edit_latency"],
        "suggest_latency": agg["suggest_latency"],
    }
    assert migrations_forced == 0 or rec["migrations"] >= 1
    for metric in ("tokens_exact", "suggestions_exact", "leak_free",
                   "migrations", "failovers", "edits_acked", "hot_hit_rate",
                   "edits_per_s", "edit_p99_ms"):
        val = rec[metric]
        val = f"{val:.3f}" if isinstance(val, float) else val
        print(f"fleet_load,{n_replicas},{metric},{val}")
    return rec


def run(full: bool = False, seed: int = 0) -> list[dict]:
    from repro.common.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    sizes = (1, 2, 4) if full else (2,)
    records = [run_fleet(n_replicas=n, seed=seed) for n in sizes]
    out = os.path.join(ensure_results(), "BENCH_fleet_load.json")
    with open(out, "w") as f:
        json.dump(records, f, indent=2)
    print(f"fleet_load,written,{out}")
    return records


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="sweep 1/2/4 replicas (default: gated 2-replica)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    run(full=args.full, seed=args.seed)
