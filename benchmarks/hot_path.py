"""Hot-path census: launches, compile counts, achieved-vs-roofline FLOPs.

ISSUE 7 satellite: the fused ragged hot path's wins are STRUCTURAL —
fewer device launches per edit step, fewer compiled shapes per stream, a
higher fraction of each step's arithmetic doing algorithmically-necessary
work — and all three are deterministic for a fixed jax version, so CI can
hold them like op counts (``check_regression``), where wall-clock cannot
be held (runner noise).

For each probed ``(B, n_cap)`` bucket this bench lowers + compiles the
batched edit step twice — fused kernel ON and OFF — and records, from the
compiled module itself (never a timer):

* ``launches`` / ``fusions`` / ``custom_calls`` — the
  ``launch/hlo_stats.launch_stats`` census of the optimized HLO;
* ``xla_flops`` / ``xla_bytes`` — XLA ``cost_analysis()``;
* ``useful_flop_fraction`` — analytic incremental-algorithm FLOPs
  (``launch/roofline.edit_step_flops``) over the XLA count;
* ``compiled_shapes_structural_stream`` — compiled-step shapes a seeded
  grow-heavy stream needs end-to-end under the serving scheduler (the
  ragged-bucketing win: capacity classes collapse the lattice).

Records MERGE by key into ``results/BENCH_hot_path.json``: the CI
bench-gate runs the single-device leg and then a forced-4-device leg
(``--mesh4``) in a second process, which appends its records to the same
file before the gate reads it.
"""
from __future__ import annotations

import json
import os

import numpy as np

from benchmarks.common import ensure_results

OUT = "BENCH_hot_path.json"


def _merge_write(records: list[dict]) -> str:
    """Merge-by-key into results/BENCH_hot_path.json (second-process legs
    append without clobbering the first leg's records)."""
    out = os.path.join(ensure_results(), OUT)
    merged: dict[str, dict] = {}
    if os.path.exists(out):
        with open(out) as f:
            merged = {r["workload"]: r for r in json.load(f)}
    for r in records:
        merged[r["workload"]] = r
    rows = [merged[k] for k in sorted(merged)]
    with open(out, "w") as f:
        json.dump(rows, f, indent=2)
    print(f"wrote {out} ({len(rows)} rows)")
    return out


def _step_census(eng, B: int, n_cap: int, C: int, R: int,
                 d_ff: int = 0) -> dict:
    """Lower + compile one batched edit step; read its HLO and cost model."""
    import jax.numpy as jnp

    from repro.launch.hlo_stats import launch_stats
    from repro.launch.roofline import edit_step_roofline

    state = eng.batch_full_forward(
        jnp.zeros((B, n_cap), jnp.int32),
        jnp.tile(jnp.arange(n_cap, dtype=jnp.int32) * 3, (B, 1)))
    bucket = jnp.full((B, C), -1, jnp.int32)
    z = jnp.zeros((B, C), jnp.int32)
    if eng.n_shards > 1:  # the sharded dispatch path (shard_map over mesh)
        lowered = eng._sharded("apply_edits").lower(eng.wts, state, bucket,
                                                    z, z, z)
    else:
        lowered = eng._batch_apply_edits_local.jitted.lower(
            eng, eng.wts, state, bucket, z, z, z)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    xla_flops = float(ca.get("flops", 0.0))
    xla_bytes = float(ca.get("bytes accessed", 0.0))
    st = launch_stats(compiled.as_text())
    # cost_analysis() prices the per-device program: under shard_map each
    # device runs B / n_shards document rows, so the analytic side must
    # price the same per-device slice for the fraction to be meaningful.
    # The peaks are the v5e's, the chip the step is built for, whatever
    # device runs the census.
    rl = edit_step_roofline(
        eng.L, eng.meta, n_cap, C, R, batch=B // eng.n_shards, d_ff=d_ff,
        xla_flops=xla_flops, xla_bytes=xla_bytes, device_kind="TPU v5 lite")
    return {**st.summary(), **rl.summary()}


def _structural_shape_count(params, cfg, *, n_edits: int, seed: int,
                            legacy: bool) -> dict:
    """Compiled shapes + launches a grow/defrag-heavy stream costs under
    the scheduler (insert-heavy so documents cross capacity boundaries)."""
    from repro.core.edits import Edit
    from repro.serving.batch_server import BatchServer

    flags = (dict(use_fused_kernel=False, capacity_class_step=2,
                  device_grow=False, device_defrag=False) if legacy else {})
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=16,
                      max_batch=2, min_doc_capacity=8, pos_pool=256, **flags)
    rng = np.random.default_rng(seed)
    srv.open_documents(
        {"a": list(rng.integers(1, cfg.vocab, 6)),
         "b": list(rng.integers(1, cfg.vocab, 6))})
    for i in range(n_edits):
        did = "ab"[int(rng.integers(2))]
        n = srv.docs[did].n_virtual
        if rng.random() < 0.7:
            srv.submit_edit(did, Edit("insert", int(rng.integers(n + 1)),
                                      int(rng.integers(1, cfg.vocab))))
        else:
            srv.submit_edit(did, Edit("replace", int(rng.integers(n)),
                                      int(rng.integers(1, cfg.vocab))))
        srv.flush()
    return {
        "compiled_shapes_structural_stream": srv.stats.traced_shapes,
        "kernel_launches_per_edit": round(
            srv.stats.kernel_launches / max(srv.stats.edits_applied, 1), 3),
        "device_grows": srv.stats.device_grows,
        "device_defrags": srv.stats.device_defrags,
    }


def run(doc_len: int = 64, n_edits: int = 24, seed: int = 0,
        mesh_tag: str = "") -> list[dict]:
    import jax

    from repro.configs.vq_opt_125m import smoke_config
    from repro.models import transformer as T
    from repro.serving.batch_engine import BatchedJitEngine

    cfg = smoke_config(vqt=True)
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    C, R = 4, 16
    records = []

    mesh = None
    if mesh_tag:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh()
    for fused in (True, False):
        eng = BatchedJitEngine(params, cfg, edit_capacity=C, row_capacity=R,
                               use_fused_kernel=fused, mesh=mesh)
        B = max(2, eng.n_shards)
        rec = {
            "workload": f"{mesh_tag or 'dev1'}_{'fused' if fused else 'unfused'}",
            "doc_len": doc_len, "B": B, "n_cap": doc_len, "C": C, "R": R,
            **_step_census(eng, B, doc_len, C, R, d_ff=cfg.d_ff),
        }
        records.append(rec)
    # scheduler-level shape census is single-device (mesh legs share it)
    if not mesh_tag:
        for legacy in (False, True):
            key = "stream_legacy" if legacy else "stream_fused"
            rec = {"workload": key, "doc_len": doc_len, "n_edits": n_edits,
                   **_structural_shape_count(params, cfg, n_edits=n_edits,
                                             seed=seed, legacy=legacy)}
            records.append(rec)
        fused_launch = next(r for r in records
                            if r["workload"].endswith("_fused")
                            and "launches" in r)["launches"]
        unfused_launch = next(r for r in records
                              if r["workload"].endswith("_unfused"))["launches"]
        print(f"hot_path,launches,fused={fused_launch},"
              f"unfused={unfused_launch}")
    for r in records:
        if "useful_flop_fraction" in r:
            print(f"hot_path,{r['workload']},launches={r['launches']},"
                  f"useful_flop_fraction={r['useful_flop_fraction']}")
        else:
            print(f"hot_path,{r['workload']},"
                  f"shapes={r['compiled_shapes_structural_stream']},"
                  f"launches_per_edit={r['kernel_launches_per_edit']}")
    _merge_write(records)
    return records


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh4", action="store_true",
                    help="forced-multi-device leg: records merge into the "
                    "same BENCH_hot_path.json under a mesh4_ key prefix")
    ap.add_argument("--doc-len", type=int, default=64)
    ap.add_argument("--n-edits", type=int, default=24)
    args = ap.parse_args()
    run(doc_len=args.doc_len, n_edits=args.n_edits,
        mesh_tag="mesh4" if args.mesh4 else "")
