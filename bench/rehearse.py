#!/usr/bin/env python3
"""Compile a configuration's serving programs for a described TPU v5e, with
no chip: the batched edit step at a row capacity, and the batched ingest,
at the configuration's widths, as shapes alone. Prints each program's
``memory_analysis`` (bytes of arguments, outputs and temporaries on one
chip). Nothing runs, so it says nothing about results or times.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --config vq-opt-1.3b \
        --n-cap 1024 --rows 64 1024
"""
import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def weight_specs(cfg, sharding):
    import jax
    import jax.numpy as jnp

    L, d, H = cfg.n_layers, cfg.d_model, cfg.n_heads
    dh, F, V = cfg.resolved_head_dim, cfg.d_ff, cfg.vocab
    hq, Q = cfg.vqt.n_heads, cfg.vqt.codebook_size

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    W = dict(ln1_s=S(L, d), ln1_b=S(L, d), wq=S(L, d, H, dh), bq=S(L, H, dh),
             wk=S(L, d, H, dh), bk=S(L, H, dh), wv=S(L, d, H, dh),
             bv=S(L, H, dh), bo=S(L, d), ln2_s=S(L, d), ln2_b=S(L, d),
             w_up=S(L, d, F), b_up=S(L, F), w_down=S(L, F, d), b_down=S(L, d),
             cb_per_head=S(L, H, Q, dh), vq_bias=S(L, hq, Q),
             c_wo=S(L, hq, Q, d))
    extras = dict(tok_emb=S(V, d), pos_emb=S(cfg.pos_pool, d), fn_s=S(d),
                  fn_b=S(d), head_w=S(d, V))
    meta = dict(H=H, dh=dh, d=d, hq=hq, Q=Q, heads_per_vq=H // hq,
                scale=float(dh ** -0.5))
    return W, extras, meta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--n-cap", type=int, default=1024)
    ap.add_argument("--rows", type=int, nargs="+", default=[64])
    ap.add_argument("--batch", type=int, default=None,
                    help="documents per dispatch (default: max_batch)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.fused_step.ops as fused_ops
    import run
    from repro.serving.batch_engine import BatchedJitEngine

    jax.config.update("jax_enable_compilation_cache", False)
    fused_ops.interpret_mode = lambda: False  # compile the real kernel
    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        conf = json.load(f)
    cfg = run.reference(conf["reference"]).arch_config(conf["model"])
    s = conf["serving"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    specs = weight_specs(cfg, chip)
    B, n, C = args.batch or s["max_batch"], args.n_cap, s["edit_capacity"]

    def report(name, compiled):
        mem = compiled.memory_analysis()
        print(json.dumps({
            "program": name, "config": args.config,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "kernel": "tpu_custom_call" in compiled.as_text()}), flush=True)

    base = BatchedJitEngine({}, cfg, edit_capacity=C, row_capacity=s[
        "row_capacity"], use_fused_kernel=True, _weights=specs)
    ids = jax.ShapeDtypeStruct((B, n), jnp.int32, sharding=chip)
    mask = jax.ShapeDtypeStruct((B, n), bool, sharding=chip)
    report(f"batch_full_forward B={B} n_cap={n}",
           base._batch_full_forward_local.jitted.lower(
               base, base.wts, ids, ids, mask).compile())
    slots = jax.ShapeDtypeStruct((n,), jnp.int32)
    one = jax.eval_shape(base._full_forward_impl, base.wts, slots, slots,
                         jax.ShapeDtypeStruct((n,), bool))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (B,) + a.shape, a.dtype, sharding=chip), one)
    bucket = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=chip)
    for R in args.rows:
        eng = BatchedJitEngine({}, cfg, edit_capacity=C, row_capacity=R,
                               use_fused_kernel=True, _weights=specs)
        report(f"batch_apply_edits B={B} n_cap={n} C={C} R={R}",
               eng._batch_apply_edits_local.jitted.lower(
                   eng, eng.wts, state, bucket, bucket, bucket,
                   bucket).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
