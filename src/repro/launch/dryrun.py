import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import — jax locks the
# device count on first init. That also rules out `from __future__` here.
"""Multi-pod dry-run: ``.lower().compile()`` every (architecture × input
shape × mesh) combination on placeholder devices, and extract the roofline
inputs from the compiled artifacts.

Per combination this produces:

  * the *sharding-correctness proof*: the full scanned model train/prefill/
    serve step compiles on the (16,16) single-pod mesh and the (2,16,16)
    multi-pod mesh;
  * ``memory_analysis()`` (per-device bytes) and ``cost_analysis()`` of that
    compile;
  * compositional FLOPs / bytes / collective-bytes: XLA's cost analysis
    counts a ``while`` (lax.scan) body ONCE regardless of trip count
    (verified empirically), so per-stage layer bodies and the trunk are each
    lowered and compiled separately on the same mesh and combined as

        total = trunk + Σ_stages repeat_i × body_i          (§Roofline)

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  python -m repro.launch.dryrun --all --out results/dryrun.jsonl
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_arch_names, get_config
from repro.configs.base import ArchConfig
from repro.distributed.context import use_mesh
from repro.launch.hlo_stats import collective_stats
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import chip_peaks
from repro.launch.sharding import batch_shardings, cache_shardings, param_shardings
from repro.launch.specs import SHAPES, ShapeCfg, decode_token_specs, input_specs, shape_supported
from repro.models import transformer as T
from repro.serving.decode import make_serve_step
from repro.training import make_schedule, make_train_step, train_state_init

# the production mesh is TPU v5e (launch/mesh.py)
PEAKS = chip_peaks("TPU v5 lite")


# --------------------------------------------------------------- step builders


def _loss_like(cfg: ArchConfig):
    sched = make_schedule(peak_lr=3e-4, warmup_steps=100, total_steps=10_000)
    return make_train_step(cfg, sched)


def _prefill_fn(cfg: ArchConfig):
    def prefill(params, batch):
        logits, _ = T.forward(
            params, cfg, batch["tokens"], batch.get("positions"),
            patch_embeds=batch.get("patch_embeds"),
        )
        return logits[:, -1]  # next-token logits for the batch

    return prefill


# --------------------------------------------------------------- lowering


def _compile_and_stats(lowered) -> dict:
    t0 = time.time()
    compiled = lowered.compile()
    dt = time.time() - t0
    ca = compiled.cost_analysis() or {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(ma, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem = {"error": str(e)}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_stats(hlo)
    return {
        "compile_s": round(dt, 2),
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collective_bytes": int(coll.total_bytes),
        "collectives": coll.summary(),
        "memory": mem,
    }


def _state_specs(cfg: ArchConfig, dtype=jnp.bfloat16):
    return jax.eval_shape(partial(train_state_init, cfg=cfg, dtype=dtype),
                          jax.random.PRNGKey(0))


def lower_full(cfg: ArchConfig, shape: ShapeCfg, mesh) -> dict:
    """The sharding-correctness proof: the complete scanned step."""
    with use_mesh(mesh):
        if shape.kind == "train":
            state_sds = _state_specs(cfg)
            batch_sds = input_specs(cfg, shape)
            in_sh = (param_shardings(state_sds, mesh), batch_shardings(batch_sds, mesh))
            step = _loss_like(cfg)
            lowered = jax.jit(step, in_shardings=in_sh).lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            params_sds = jax.eval_shape(
                partial(T.init_params, cfg=cfg, dtype=jnp.bfloat16), jax.random.PRNGKey(0)
            )
            batch_sds = input_specs(cfg, shape)
            in_sh = (param_shardings(params_sds, mesh), batch_shardings(batch_sds, mesh))
            lowered = jax.jit(_prefill_fn(cfg), in_shardings=in_sh).lower(
                params_sds, batch_sds
            )
        else:  # decode
            params_sds = jax.eval_shape(
                partial(T.init_params, cfg=cfg, dtype=jnp.bfloat16), jax.random.PRNGKey(0)
            )
            caches_sds = jax.eval_shape(
                partial(T.init_caches, cfg, shape.global_batch, shape.seq_len,
                        dtype=jnp.bfloat16)
            )
            tok = decode_token_specs(cfg, shape)
            in_sh = (
                param_shardings(params_sds, mesh),
                cache_shardings(caches_sds, mesh, batch=shape.global_batch),
                batch_shardings({"tokens": tok["tokens"]}, mesh)["tokens"],
                batch_shardings({"positions": tok["positions"]}, mesh)["positions"],
            )
            step = make_serve_step(cfg)
            # donate the caches: the decode loop always overwrites them, and
            # without aliasing every one-token update costs a whole-cache
            # copy (§Perf iteration 6)
            lowered = jax.jit(step, in_shardings=in_sh, donate_argnums=(1,)).lower(
                params_sds, caches_sds, tok["tokens"], tok["positions"]
            )
        return _compile_and_stats(lowered)


# ------------------------------------------------- compositional roofline


def _stage_param_sds(cfg: ArchConfig, dtype=jnp.bfloat16):
    params_sds = jax.eval_shape(
        partial(T.init_params, cfg=cfg, dtype=dtype), jax.random.PRNGKey(0)
    )
    return params_sds


def _one_stage_body(cfg: ArchConfig, si: int, *, train: bool):
    pattern, _ = cfg.stages[si]

    def body(sp, x, positions):
        aux = jnp.zeros((), jnp.float32)
        for pi, layer in enumerate(pattern):
            x, a = T._layer_fwd(
                sp[pi], cfg, layer, x, positions, train=train,
                vq_rng=jax.random.PRNGKey(0) if train else None,
            )
            aux = aux + a
        return x, aux

    if not train:
        return body

    def train_body(sp, x, positions):
        def loss(sp_, x_):
            import os as _os
            if _os.environ.get("REMAT_POLICY", "full") == "dots":
                ckpt = jax.checkpoint(
                    body,
                    policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                )
            else:
                ckpt = jax.checkpoint(body)
            y, aux = ckpt(sp_, x_, positions)
            return jnp.sum(y.astype(jnp.float32)) + aux

        g_sp, g_x = jax.grad(loss, argnums=(0, 1))(sp, x)
        return g_sp, g_x

    return train_body


def _decode_stage_body(cfg: ArchConfig, si: int):
    pattern, _ = cfg.stages[si]

    def body(sp, cache, x, positions):
        new = []
        for pi, layer in enumerate(pattern):
            x, mc = T._layer_decode(sp[pi], cfg, layer, x, cache[pi], positions)
            new.append(mc)
        return x, tuple(new)

    return body


def _trunk_fns(cfg: ArchConfig, shape: ShapeCfg):
    """Embedding + head (+ loss/opt for train) without any layers."""
    if shape.kind == "train":

        def trunk(params, batch):
            b = batch["tokens"].shape[0]
            n = batch["tokens"].shape[1]
            pos = batch.get("positions")
            if pos is None:
                pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
            x = T.embed_tokens(params["embed"], cfg, batch["tokens"], pos)
            if cfg.input_mode == "vlm":
                x = T.merge_vision(params["embed"], batch["patch_embeds"], x)

            def loss(p, x_):
                logits = T._head(p, cfg, x_)
                from repro.training.losses import next_token_loss

                return next_token_loss(logits[:, -batch["tokens"].shape[1]:],
                                       batch["tokens"])

            l, (gp, gx) = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
            return l, gp["final_norm"], gx

        return trunk

    def trunk(params, batch):
        b = batch["tokens"].shape[0]
        n = batch["tokens"].shape[1]
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
        x = T.embed_tokens(params["embed"], cfg, batch["tokens"], pos)
        if cfg.input_mode == "vlm" and "patch_embeds" in batch:
            x = T.merge_vision(params["embed"], batch["patch_embeds"], x)
        logits = T._head(params, cfg, x)
        return logits[:, -1]

    return trunk


def lower_roofline(cfg: ArchConfig, shape: ShapeCfg, mesh) -> dict:
    """Compositional FLOPs/bytes/collectives: trunk + Σ repeat × stage body."""
    total = {"flops": 0.0, "bytes": 0.0, "collective_bytes": 0}
    parts = {}
    with use_mesh(mesh):
        params_sds = _stage_param_sds(cfg)
        p_sh = param_shardings(params_sds, mesh)
        b, n = shape.global_batch, shape.seq_len
        if cfg.input_mode == "vlm" and shape.kind != "decode":
            n_x = n  # patches already folded into the sequence for bodies
        else:
            n_x = n
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if shape.kind == "decode":
            x_sds = jax.ShapeDtypeStruct((b, 1, cfg.d_model), jnp.bfloat16)
            pos_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        else:
            x_sds = jax.ShapeDtypeStruct((b, n_x, cfg.d_model), jnp.bfloat16)
            pos_sds = jax.ShapeDtypeStruct((b, n_x), jnp.int32)
        n_data = 1
        for a in data_axes:
            n_data *= mesh.shape[a]
        # between layers the residual stream is sequence-sharded on "model"
        # (§Perf iteration 5), so stage bodies are lowered with that input
        # sharding — matches the steady state of the full scanned model
        n_model = mesh.shape.get("model", 1)
        seq_len_x = x_sds.shape[1]
        seq_spec = "model" if (shape.kind != "decode" and seq_len_x % n_model == 0) else None
        if b >= n_data and b % n_data == 0:
            x_spec = NamedSharding(mesh, P(data_axes, seq_spec, None))
            pos_spec = NamedSharding(mesh, P(data_axes, None))
        else:
            x_spec = NamedSharding(mesh, P(None, seq_spec, None))
            pos_spec = NamedSharding(mesh, P(None, None))

        # --- per-stage bodies ---
        for si, (pattern, repeat) in enumerate(cfg.stages):
            sp_sds = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                params_sds.params["stages"][si]
                if hasattr(params_sds, "params")
                else params_sds["stages"][si],
            )
            sp_sh = jax.tree.map(
                lambda s: NamedSharding(
                    mesh, P(*s.spec[1:])
                ),
                (p_sh.params["stages"][si] if hasattr(p_sh, "params")
                 else p_sh["stages"][si]),
            )
            if shape.kind == "decode":
                caches_sds = jax.eval_shape(
                    partial(T.init_caches, cfg, b, shape.seq_len, dtype=jnp.bfloat16)
                )
                c_sh_full = cache_shardings(caches_sds, mesh, batch=b)
                c_sds = jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    caches_sds[si],
                )
                c_sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, P(*s.spec[1:])), c_sh_full[si]
                )
                fn = _decode_stage_body(cfg, si)
                lowered = jax.jit(
                    fn, in_shardings=(sp_sh, c_sh, x_spec, pos_spec),
                    donate_argnums=(1,),  # §Perf iteration 6: alias caches
                ).lower(sp_sds, c_sds, x_sds, pos_sds)
            else:
                fn = _one_stage_body(cfg, si, train=(shape.kind == "train"))
                lowered = jax.jit(
                    fn, in_shardings=(sp_sh, x_spec, pos_spec)
                ).lower(sp_sds, x_sds, pos_sds)
            st = _compile_and_stats(lowered)
            parts[f"stage{si}(x{repeat})"] = st
            total["flops"] += repeat * st["flops"]
            total["bytes"] += repeat * st["bytes"]
            total["collective_bytes"] += repeat * st["collective_bytes"]

        # --- trunk ---
        if shape.kind == "decode":
            batch_sds = decode_token_specs(cfg, shape)
        else:
            batch_sds = input_specs(cfg, shape)
        trunk = _trunk_fns(cfg, shape if shape.kind == "train" else
                           ShapeCfg(shape.name, "prefill", shape.seq_len if
                                    shape.kind != "decode" else 1, b))
        b_sh = batch_shardings(batch_sds, mesh)
        lowered = jax.jit(
            trunk,
            in_shardings=(
                param_shardings(
                    params_sds.params if hasattr(params_sds, "params") else params_sds,
                    mesh,
                ),
                b_sh,
            ),
        ).lower(
            params_sds.params if hasattr(params_sds, "params") else params_sds,
            batch_sds,
        )
        st = _compile_and_stats(lowered)
        parts["trunk"] = st
        total["flops"] += st["flops"]
        total["bytes"] += st["bytes"]
        total["collective_bytes"] += st["collective_bytes"]
    return {"total": total, "parts": parts}


# ------------------------------------------------------------ model flops


def model_flops(cfg: ArchConfig, shape: ShapeCfg) -> float:
    """MODEL_FLOPS = 6·N_active·D (spec §Roofline)."""
    params_sds = jax.eval_shape(
        partial(T.init_params, cfg=cfg, dtype=jnp.bfloat16), jax.random.PRNGKey(0)
    )
    n_total = 0
    n_moe_all = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params_sds)[0]:
        size = 1
        for s in leaf.shape:
            size *= s
        names = [str(getattr(p, "key", getattr(p, "name", getattr(p, "idx", p))))
                 for p in path]
        if cfg.moe and any(n in ("w_gate", "w_up", "w_down") for n in names) and len(
            leaf.shape
        ) == 4:
            n_moe_all += size
        else:
            n_total += size
    n_active = n_total
    if cfg.moe and n_moe_all:
        n_active += n_moe_all * (cfg.moe.top_k / cfg.moe.n_experts)
    D = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    return mult * n_active * D


# ------------------------------------------------------------------ driver


def run_one(arch: str, shape_name: str, *, multi_pod: bool, roofline: bool) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        rec["full"] = lower_full(cfg, shape, mesh)
        rec["status"] = "ok"
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec
    if roofline and not multi_pod:
        try:
            rl = lower_roofline(cfg, shape, mesh)
            # cost_analysis() reports the PARTITIONED (per-device) module
            # (verified empirically), so each term is per-device work over
            # per-chip rate — the roofline time of the parallel step.
            chips = 256
            t = rl["total"]
            terms = {
                "compute_s": t["flops"] / PEAKS["flops"],
                "memory_s": t["bytes"] / PEAKS["hbm_bytes_per_s"],
                "collective_s": (t["collective_bytes"]
                                 / PEAKS["ici_bytes_per_s_per_link"]),
            }
            terms["bottleneck"] = max(terms, key=lambda k: terms[k])
            mf = model_flops(cfg, shape)
            terms["model_flops"] = mf
            terms["useful_ratio"] = mf / (t["flops"] * chips) if t["flops"] else 0.0
            rec["roofline"] = {**rl, "terms": terms}
        except Exception as e:
            rec["roofline_error"] = f"{type(e).__name__}: {e}"
            rec["roofline_traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = all_arch_names()[:10] if args.all else [args.arch]
    shapes = list(SHAPES) if args.all else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    out_f = open(args.out, "a") if args.out else None
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                t0 = time.time()
                rec = run_one(arch, shape_name, multi_pod=mp,
                              roofline=args.roofline and not mp)
                rec["wall_s"] = round(time.time() - t0, 1)
                line = json.dumps(rec)
                print(f"[{rec['status']:>7}] {arch} {shape_name} {rec['mesh']} "
                      f"({rec['wall_s']}s)"
                      + (f" err={rec.get('error','')}" if rec["status"] == "error" else ""),
                      flush=True)
                if out_f:
                    out_f.write(line + "\n")
                    out_f.flush()
    if out_f:
        out_f.close()


if __name__ == "__main__":
    main()
