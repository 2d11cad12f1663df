"""Mixture-of-Experts with expert parallelism (DeepSeek-V2/V3 style).

Two execution paths:

* ``moe_apply_dense`` — reference path (loops over experts, mask-weighted).
  Used on single devices (smoke tests) and as the correctness oracle.
* ``moe_apply_ep`` — shard_map expert parallelism over the ``model`` mesh
  axis. Tokens are split across the model axis (on top of their data-axis
  sharding), routed to expert-owner devices through a fixed-capacity
  ``all_to_all`` (cumsum slotting, no dynamic sort), run through the local
  experts as one grouped einsum, routed back with the inverse ``all_to_all``,
  and combined with the router gates. Shared experts run densely on all
  tokens. Fixed capacity means tokens beyond ``capacity_factor`` are dropped
  (standard for TPU MoE, cf. Switch/GShard/MaxText).

With the paper's VQT feature enabled, the *inputs* to the router are
vector-quantized activations, so identical codes route identically — the
incremental serving engine exploits this to dedup expert compute across
revisions (see DESIGN.md §4, a beyond-paper amplification of the technique).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.context import get_ctx
from repro.models.ffn import ffn_apply, ffn_init


def moe_init(key: jax.Array, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    e = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e.n_experts)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e.n_experts, d, e.d_ff_expert)) * s).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e.n_experts, d, e.d_ff_expert)) * s).astype(dtype),
        "w_down": (
            jax.random.normal(ks[3], (e.n_experts, e.d_ff_expert, d)) * e.d_ff_expert ** -0.5
        ).astype(dtype),
    }
    if e.n_shared > 0:
        p["shared"] = ffn_init(ks[4], "swiglu", d, e.n_shared * e.d_ff_expert, dtype)
    return p


def _router(params: dict, e, x: jax.Array):
    """x: [T, d] -> (gates [T, k], eidx [T, k], aux_loss scalar)."""
    logits = x.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)  # renorm (DeepSeek)
    # Switch-style load-balance loss.
    frac_prob = probs.mean(axis=0)  # [E]
    assign = jax.nn.one_hot(eidx, e.n_experts, dtype=jnp.float32).sum(axis=1)  # [T, E]
    frac_tok = assign.mean(axis=0) / e.top_k
    aux = e.n_experts * jnp.sum(frac_prob * frac_tok) * e.aux_loss_weight
    return gates, eidx, aux


def _expert_ffn(w_gate, w_up, w_down, xs):
    """xs: [E_loc, C, d] grouped tokens; weights [E_loc, ...]."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate))
    h = g * jnp.einsum("ecd,edf->ecf", xs, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_apply_dense(params: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Reference: x [b, n, d] -> (y, aux). Loops over all experts."""
    e = cfg.moe
    b, n, d = x.shape
    xt = x.reshape(-1, d)
    gates, eidx, aux = _router(params, e, xt)
    y = jnp.zeros_like(xt)

    def body(i, y):
        w = (eidx == i).astype(x.dtype) * gates.astype(x.dtype)  # [T, k]
        wi = w.sum(-1, keepdims=True)  # [T, 1]
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i, 1, axis=0)
        fe = _expert_ffn(
            sl(params["w_gate"]), sl(params["w_up"]), sl(params["w_down"]), xt[None]
        )[0]
        return y + fe * wi

    y = jax.lax.fori_loop(0, e.n_experts, body, y)
    if "shared" in params:
        y = y + ffn_apply("swiglu", params["shared"], xt)
    return y.reshape(b, n, d), aux


def _ep_capacity(t2: int, e, n_experts: int) -> int:
    cap = int(math.ceil(t2 * e.top_k / n_experts * e.capacity_factor))
    return max(8, cap)


def moe_apply_ep(params: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via shard_map. x: [b, n, d] sharded on batch axes."""
    ctx = get_ctx()
    if ctx is None:
        return moe_apply_dense(params, cfg, x)
    mesh = ctx.mesh
    e = cfg.moe
    b, n, d = x.shape
    M = mesh.shape.get("model", 1)
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    E = e.n_experts
    assert E % M == 0, f"experts {E} must divide model axis {M}"
    E_loc = E // M

    tok_spec = P(data_axes if data_axes else None, None, None)
    # router weights replicated; expert weights sharded over model on axis 0.
    param_specs = {
        "router": P(None, None),
        "w_gate": P("model", None, None),
        "w_up": P("model", None, None),
        "w_down": P("model", None, None),
    }
    if "shared" in params:
        param_specs["shared"] = {k: P(None, "model") if k != "w_down" else P("model", None)
                                 for k in params["shared"]}

    def local_moe(p, xb):
        # xb: [b_loc, n, d] local tokens (replicated over model axis).
        b_loc = xb.shape[0]
        xt = xb.reshape(-1, d)
        T_loc = xt.shape[0]
        T2 = -(-T_loc // M)  # tokens this model-slice is responsible for
        pad = T2 * M - T_loc
        if pad:
            xt = jnp.concatenate([xt, jnp.zeros((pad, d), xt.dtype)], axis=0)
        midx = jax.lax.axis_index("model")
        x_mine = jax.lax.dynamic_slice_in_dim(xt, midx * T2, T2, axis=0)  # [T2, d]
        gates, eidx, aux = _router(p, e, x_mine)  # [T2, k]
        cap = _ep_capacity(T2, e, E)
        # --- dispatch: slot each assignment into its expert bucket ---
        flat_e = eidx.reshape(-1)  # [T2*k]
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T2*k, E]
        rank = jnp.cumsum(onehot, axis=0) - onehot  # prior count
        pos = jnp.sum(rank * onehot, axis=1)  # [T2*k] position within bucket
        keep = pos < cap
        src = jnp.repeat(jnp.arange(T2), e.top_k)
        buf = jnp.zeros((E, cap, d), xt.dtype)
        buf = buf.at[flat_e, jnp.minimum(pos, cap - 1)].add(
            x_mine[src] * keep[:, None].astype(xt.dtype), mode="drop"
        )
        # --- all_to_all to expert owners: [E, cap, d] -> [M, E_loc, cap, d] ---
        buf = buf.reshape(M, E_loc, cap, d)
        recv = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=0, tiled=False)
        # recv: [M_src, E_loc, cap, d] -> group per expert
        grouped = jnp.moveaxis(recv, 0, 1).reshape(E_loc, M * cap, d)
        out_g = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], grouped)
        # --- route back ---
        back = jnp.moveaxis(out_g.reshape(E_loc, M, cap, d), 1, 0)  # [M, E_loc, cap, d]
        ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0, tiled=False)
        ret = ret.reshape(E, cap, d)  # my tokens' expert outputs
        vals = ret[flat_e, jnp.minimum(pos, cap - 1)] * keep[:, None].astype(xt.dtype)
        w = gates.reshape(-1)[:, None].astype(xt.dtype)
        y_mine = jnp.zeros((T2, d), xt.dtype).at[src].add(vals * w)
        # --- reassemble across the model axis ---
        y_all = jax.lax.all_gather(y_mine, "model", axis=0, tiled=True)  # [T2*M, d]
        y = y_all[:T_loc]
        if "shared" in p:
            y = y + ffn_apply("swiglu", p["shared"], xb.reshape(-1, d))
        aux = jax.lax.pmean(aux, "model")
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
        return y.reshape(b_loc, n, d), aux

    # the replication checker is off: it cannot always prove the
    # replicated expert weights' outputs
    y, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(param_specs, tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )({k: params[k] for k in param_specs}, x)
    return y, aux


def moe_apply(params: dict, cfg: ArchConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    return moe_apply_ep(params, cfg, x)


def moe_per_code(params: dict, cfg: ArchConfig, c) -> tuple:
    """MoE over a *compressed* activation tensor (DESIGN.md §4, the
    beyond-paper amplification): identical VQ codes route identically, so
    routing + expert FFN run once per unique codebook row — O(q) instead of
    O(b·n) expert compute across a batch of revisions.

    c: repro.core.compressed.Compressed. Returns (Compressed y, aux)."""
    from repro.core.compressed import Compressed

    y_rows, aux = moe_apply_dense(params, cfg, c.codebook[None])
    return Compressed(y_rows[0], c.idx, c.n_codes), aux
