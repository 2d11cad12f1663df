"""Benchmark regression gate (ISSUE 4 satellite).

Compares fresh benchmark emissions (``results/BENCH_*.json``) against
committed baselines (``results/BASELINE_*.json``) and exits non-zero when a
gated metric regresses beyond its stated tolerance — CI runs this after the
benchmark smoke steps, so a PR cannot silently trade away ops-saved ratio,
prefill reuse, or oracle exactness.

Gate policy:

* only DETERMINISTIC metrics are gated (op counts, reuse fractions,
  traced-shape counts, oracle-match booleans) — wall-clock fields are
  reported but never gated (CI runner noise). The ONE exception is
  same-runner wall-clock *ratios* (``refresh_to_oracle_ratio``): both legs
  run interleaved on the same machine in the same process with synced,
  warmed timing, so runner speed divides out — gated with a wide abs_tol
  plus a hard ``must_be_lt`` ceiling encoding the SLO itself ("incremental
  refresh beats the from-scratch oracle");
* direction-aware: a metric only fails in its *worse* direction, beyond
  ``max(abs_tol, rel_tol * baseline)``; improvements always pass (and are
  listed, so a re-anchor can ratchet the baseline);
* identity fields (workload, doc_len, n_edits, ...) must match the baseline
  exactly — a param drift between CI and the committed baseline is a gate
  misconfiguration, reported as an error rather than a pass.

Usage::

    python -m benchmarks.check_regression            # gate (exit 1 on fail)
    python -m benchmarks.check_regression --update   # re-anchor baselines
    python -m benchmarks.check_regression --results-dir path/to/results

Re-anchoring: run the benchmarks at the gate params (see .github/workflows/
ci.yml), inspect the fresh numbers, then ``--update`` to copy every gated
``BENCH_*.json`` over its ``BASELINE_*.json``. ``results/SUMMARY.json``
(written by ``benchmarks.run``) carries the same records for full-protocol
re-anchors.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

# metric -> {higher_is_better, rel_tol, abs_tol} | {must_equal};
# an optional must_be_lt adds a hard ceiling on top of the baseline delta
# check (fails when fresh >= ceiling, regardless of the baseline)
GATES = {
    "edit_mix": {
        "bench": "BENCH_edit_mix.json",
        "baseline": "BASELINE_edit_mix.json",
        "key": "workload",
        "identity": ("doc_len", "n_edits"),
        "metrics": {
            "ops_speedup": {"higher_is_better": True, "rel_tol": 0.10},
            "ops_incremental": {"higher_is_better": False, "rel_tol": 0.10},
            "traced_shapes": {"higher_is_better": False, "abs_tol": 2},
            # ISSUE 7: the warm measured pass must compile NOTHING (the
            # warmup replays the identical trace) ...
            "measured_pass_new_shapes": {"must_equal": 0},
            # ... and a structural stream must stay within a small factor
            # of the replace-only fast path — same-runner wall-clock
            # ratio (synced + warmup-replayed), so runner speed divides
            # out; the ceiling is the fused-ragged-hot-path SLO. Only the
            # mixed record carries it (it IS the cross-workload ratio).
            "wall_ratio_mixed_vs_replace": {
                "higher_is_better": False, "abs_tol": 0.75,
                "must_be_lt": 3.0, "optional": True},
        },
    },
    # ISSUE 7 satellite: the fused hot path's structural wins, read from
    # the compiled modules themselves (launch census, XLA cost model,
    # achieved-vs-roofline fraction) and from the scheduler's shape
    # counter — all deterministic for a pinned jax version (the bench-gate
    # job pins one; re-anchor on version bumps). Wall-clock never appears.
    "hot_path": {
        "bench": "BENCH_hot_path.json",
        "baseline": "BASELINE_hot_path.json",
        "key": "workload",
        "identity": ("doc_len",),
        "metrics": {
            "launches": {"higher_is_better": False, "rel_tol": 0.15,
                         "optional": True},
            "xla_flops": {"higher_is_better": False, "rel_tol": 0.10,
                          "optional": True},
            "useful_flop_fraction": {"higher_is_better": True,
                                     "rel_tol": 0.15, "optional": True},
            "compiled_shapes_structural_stream": {
                "higher_is_better": False, "abs_tol": 0, "optional": True},
            "device_grows": {"higher_is_better": True, "abs_tol": 0,
                             "optional": True},
        },
    },
    "suggest_reuse": {
        "bench": "BENCH_suggest_reuse.json",
        "baseline": "BASELINE_suggest_reuse.json",
        "key": "workload",
        "identity": ("doc_len", "n_edits", "n_new"),
        "metrics": {
            "reused_prefill_fraction": {
                "higher_is_better": True, "rel_tol": 0.10, "abs_tol": 0.02},
            "suggestions_match_oracle": {"must_equal": True},
            # ISSUE 6: the wall-clock SLO. A same-runner ratio of medians
            # (synced + warmed timing), so runner noise divides out; the
            # must_be_lt ceiling is the acceptance criterion itself —
            # incremental refresh must beat the from-scratch oracle.
            "refresh_to_oracle_ratio": {
                "higher_is_better": False, "abs_tol": 0.15,
                "must_be_lt": 1.0},
        },
    },
    # ISSUE 6 tentpole: deadline-batching async front end. Parity bits and
    # the exact admitted-edit count are deterministic (client threads own
    # disjoint documents, so per-document streams are schedule-independent);
    # latency percentiles and rounds are reported, never gated.
    "async_load": {
        "bench": "BENCH_async_load.json",
        "baseline": "BASELINE_async_load.json",
        "key": "scenario",
        "identity": ("n_docs", "doc_len", "n_edits", "n_new"),
        "metrics": {
            "tokens_match": {"must_equal": True},
            "suggestions_match": {"must_equal": True},
            "edits_applied": {"higher_is_better": True, "abs_tol": 0},
        },
    },
    # ISSUE 10 tentpole: multi-replica fleet behind the router, with a
    # forced cross-replica migration and a forced failover mid-run. The
    # exactness/leak bits and the chaos/ack counts are deterministic
    # (seeded schedule, deterministic placement). p99/throughput are
    # wall-clock — gated ONLY with cavernous tolerances that catch
    # order-of-magnitude serving regressions, never runner noise (the
    # repo-wide wall-clock policy stands; these are smoke ceilings).
    "fleet_load": {
        "bench": "BENCH_fleet_load.json",
        "baseline": "BASELINE_fleet_load.json",
        "key": "n_replicas",
        "identity": ("n_docs", "n_sessions", "doc_len", "n_new", "seed"),
        "metrics": {
            "tokens_exact": {"must_equal": True},
            "suggestions_exact": {"must_equal": True},
            "leak_free": {"must_equal": True},
            "migrations": {"higher_is_better": True, "abs_tol": 0},
            "failovers": {"higher_is_better": True, "abs_tol": 0},
            "edits_acked": {"higher_is_better": True, "abs_tol": 0},
            "hot_hit_rate": {"higher_is_better": True, "abs_tol": 0.02},
            "edit_p99_ms": {"higher_is_better": False, "rel_tol": 5.0},
            "edits_per_s": {"higher_is_better": True, "rel_tol": 0.9},
        },
    },
    # ISSUE 4's benchmark, gated since ISSUE 5: deterministic parity bits
    # and the scheduler's placement quality (run under 4 forced host
    # devices — see the bench-gate job's XLA_FLAGS)
    "sharded_serving": {
        "bench": "BENCH_sharded_serving.json",
        "baseline": "BASELINE_sharded_serving.json",
        "key": "mesh_size",
        "identity": ("doc_len", "n_docs", "n_edits"),
        "metrics": {
            "tokens_match": {"must_equal": True},
            "oracle_match": {"must_equal": True},
            "logits_close_vs_mesh1": {"must_equal": True},
            "mean_shard_imbalance": {"higher_is_better": False,
                                     "abs_tol": 0.05},
            "batch_dispatches": {"higher_is_better": False, "abs_tol": 2},
        },
    },
    # ISSUE 9: the sigma-delta Pareto curve. Everything here is a
    # deterministic function of the seeded trace (transmitted-row counts,
    # bitwise booleans, drift vs a from-scratch oracle) — no wall-clock.
    # The gate holds the curve's SHAPE: threshold 0 stays bitwise-exact,
    # ops stay monotone nonincreasing in threshold, drift stays under the
    # documented bound (delta_pareto.DRIFT_BOUND), and the max-threshold
    # leg keeps saving its baseline fraction of transmissions.
    "delta_pareto": {
        "bench": "BENCH_delta_pareto.json",
        "baseline": "BASELINE_delta_pareto.json",
        "key": "workload",
        "identity": ("doc_len", "n_edits", "thresholds"),
        "metrics": {
            "threshold0_bitwise": {"must_equal": True},
            "ops_monotone_nonincreasing": {"must_equal": True},
            "drift_within_bound": {"must_equal": True},
            "ops_saved_frac_max_threshold": {
                "higher_is_better": True, "abs_tol": 0.05},
        },
    },
    # ISSUE 5: tiered-store churn under a zipf stream. Counters are
    # deterministic under the seeded stream; rehydrate/full-forward
    # latencies are wall-clock and never gated.
    "state_churn": {
        "bench": "BENCH_state_churn.json",
        "baseline": "BASELINE_state_churn.json",
        "key": "workload",
        "identity": ("n_docs", "doc_len", "n_edits", "budget_docs", "n_new"),
        "metrics": {
            "hot_hit_rate": {"higher_is_better": True, "abs_tol": 0.02},
            "evictions": {"higher_is_better": False, "abs_tol": 2},
            "spills": {"higher_is_better": False, "abs_tol": 2},
            "rehydrations": {"higher_is_better": False, "abs_tol": 2},
            "oracle_match": {"must_equal": True},
            "leak_free": {"must_equal": True},
        },
    },
}


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def _index(records: list, key: str) -> dict:
    return {rec[key]: rec for rec in records}


def check_gate(name: str, gate: dict, results_dir: str) -> list[str]:
    """Returns a list of failure strings (empty = gate passes)."""
    bench_path = os.path.join(results_dir, gate["bench"])
    base_path = os.path.join(results_dir, gate["baseline"])
    failures = []
    for path, kind in ((bench_path, "fresh benchmark"),
                       (base_path, "baseline")):
        if not os.path.exists(path):
            return [f"{name}: missing {kind} file {path}"]
    fresh = _index(_load(bench_path), gate["key"])
    base = _index(_load(base_path), gate["key"])
    for wk, brec in sorted(base.items()):
        frec = fresh.get(wk)
        if frec is None:
            failures.append(f"{name}/{wk}: workload missing from fresh run")
            continue
        for field in gate.get("identity", ()):
            if frec.get(field) != brec.get(field):
                failures.append(
                    f"{name}/{wk}: identity field {field} drifted "
                    f"({brec.get(field)} -> {frec.get(field)}) — regenerate "
                    "the baseline or fix the CI invocation")
        for metric, rule in gate["metrics"].items():
            have, want = frec.get(metric), brec.get(metric)
            if have is None and want is None and rule.get("optional"):
                continue  # metric legitimately absent from this workload
            if have is None or want is None:
                failures.append(f"{name}/{wk}: metric {metric} missing "
                                f"(fresh={have!r}, baseline={want!r})")
                continue
            if "must_equal" in rule:
                ok = have == rule["must_equal"]
                verdict = "ok" if ok else "REGRESSED"
                print(f"  {name}/{wk}.{metric}: {have} "
                      f"(required {rule['must_equal']}) {verdict}")
                if not ok:
                    failures.append(
                        f"{name}/{wk}: {metric}={have}, must equal "
                        f"{rule['must_equal']}")
                continue
            tol = max(rule.get("abs_tol", 0.0),
                      rule.get("rel_tol", 0.0) * abs(float(want)))
            delta = float(have) - float(want)
            worse = -delta if rule["higher_is_better"] else delta
            ok = worse <= tol
            ceiling = rule.get("must_be_lt")
            if ceiling is not None and not float(have) < ceiling:
                ok = False
                failures.append(
                    f"{name}/{wk}: {metric}={have} breaches the hard "
                    f"ceiling (must be < {ceiling})")
            verdict = "ok" if ok else "REGRESSED"
            ceil_note = f", ceiling {ceiling}" if ceiling is not None else ""
            print(f"  {name}/{wk}.{metric}: {have} vs baseline {want} "
                  f"(tol {tol:.4g}{ceil_note}) {verdict}")
            if worse > tol:
                failures.append(
                    f"{name}/{wk}: {metric} regressed {want} -> {have} "
                    f"(worse by {worse:.4g} > tol {tol:.4g})")
    return failures


def update_baselines(results_dir: str) -> int:
    rc = 0
    for name, gate in GATES.items():
        src = os.path.join(results_dir, gate["bench"])
        dst = os.path.join(results_dir, gate["baseline"])
        if not os.path.exists(src):
            print(f"{name}: cannot re-anchor, {src} missing")
            rc = 2
            continue
        shutil.copyfile(src, dst)
        print(f"{name}: {src} -> {dst}")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--results-dir", default=os.path.join(
        os.path.dirname(__file__), "..", "results"))
    ap.add_argument("--update", action="store_true",
                    help="copy fresh BENCH files over the BASELINE files")
    args = ap.parse_args(argv)
    if args.update:
        return update_baselines(args.results_dir)
    all_failures = []
    for name, gate in GATES.items():
        print(f"gate {name}:")
        all_failures += check_gate(name, gate, args.results_dir)
    if all_failures:
        print("\nREGRESSIONS:")
        for f in all_failures:
            print(f"  {f}")
        return 1
    print("\nall benchmark gates green")
    return 0


if __name__ == "__main__":
    sys.exit(main())
