"""Pallas TPU kernel: the fused incremental edit step (DESIGN.md §9).

One launch per layer replaces the old per-op chain (column-patch kernel →
host-side T accumulate → requantize einsums → argmax): per (row-block,
vq-head) grid cell the kernel

  1. applies the old-minus/new-plus attention column patch for the
     ``heads_per_vq`` attention heads feeding this vq head:

         ΔT[i, h, :] = Σ_c gelu(q[i,h]·k_new[c,h]·scale) vc_new[c,h,:]
                     − Σ_c gelu(q[i,h]·k_old[c,h]·scale) vc_old[c,h,:]

     (two MXU matmuls per head, exactly the ``incr_patch`` body);
  2. accumulates ``T = T_base + ΔT`` per head and writes it back;
  3. re-quantizes in score space: ``s = Σ_heads T / counts + vq_bias``,
     ``codes = argmax_Q s`` (VPU reduce) — the ``vq_assign`` trick without
     a second launch or an HBM round-trip of T.

Changed-column gating, causal structure, row validity and dirty-row
exclusion are all folded into one [rows, C] mask on the host side of the
same jit — the kernel body only ever multiplies by it, so its compiled
shape is blind to WHICH rows/columns are live.

Raggedness: the grid iterates over PADDED row blocks of a capacity-class
buffer; rows whose ``valid`` bit is off (free slots, the padding beyond a
document's logical capacity) have an all-zero mask row and ``counts``
clamped to 1, so one compiled step serves every logical ``n_cap`` inside
the class (``repro.common.bucketing.capacity_class``).

Head-group blocking: stacked weights order attention heads as
``h = hh * heads_per_vq + j`` (see ``_weights_from_params``'s
``cb_per_head`` reshape), so blocking the head axis by ``heads_per_vq`` at
block index ``hh`` hands each grid cell exactly the heads its vq head
sums over.

``delta_gate_kernel`` is the sigma-delta companion launch (DESIGN.md §10):
a per-row L∞ reduce + threshold compare deciding which freshly recomputed
rows propagate downstream. The resulting keep bits flow back into the
NEXT layer's engine-built mask, so the thresholded gating mode costs one
tiny extra launch per layer and zero changes to the fused patch body.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common.precision import SERVING_PRECISION


def _kernel(q_ref, kn_ref, ko_ref, vcn_ref, vco_ref, mask_ref, tb_ref,
            counts_ref, bias_ref, t_ref, codes_ref, *, scale: float, g: int):
    # Head-major blocks, so each head is a leading-axis index and the
    # trailing (sublane, lane) pair of every block is a full or 8-aligned
    # extent — the TPU tiling rule:
    # q_ref: [g, BR, dh]; kn/ko: [g, C, dh]; vcn/vco: [g, C, Q];
    # mask: [BR, C]; tb/t: [g, BR, Q]; counts: [BR, 1]; bias: [1, 1, Q];
    # codes: [1, BR, 1]
    mask = mask_ref[...].astype(jnp.float32)  # [BR, C]
    acc = None
    for j in range(g):
        q = q_ref[j]  # [BR, dh]

        def contrib(k_ref, vc_ref, sign):
            s = jax.lax.dot_general(
                q, k_ref[j], (((1,), (1,)), ((), ())),
                precision=SERVING_PRECISION, preferred_element_type=jnp.float32,
            ) * scale  # [BR, C]
            w = jax.nn.gelu(s, approximate=True) * mask
            return sign * jax.lax.dot_general(
                w, vc_ref[j].astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=SERVING_PRECISION, preferred_element_type=jnp.float32,
            )  # [BR, Q]

        Tj = (tb_ref[j].astype(jnp.float32)
              + contrib(kn_ref, vcn_ref, 1.0) + contrib(ko_ref, vco_ref, -1.0))
        t_ref[j] = Tj.astype(t_ref.dtype)
        acc = Tj if acc is None else acc + Tj
    scores = acc / counts_ref[...] + bias_ref[0]  # [BR, Q]
    # first-max argmax from 2-D reductions (jnp.argmax's tie rule) — keeps
    # the result [BR, 1] without a 1-D relayout
    top = jnp.max(scores, axis=-1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    codes_ref[0] = jnp.min(jnp.where(scores == top, lane, scores.shape[-1]),
                           axis=-1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("heads_per_vq", "block_r", "interpret"))
def fused_step_kernel(
    q: jax.Array,  # [n, H, dh] every row's cached queries
    k_new: jax.Array,  # [H, C, dh] dirty-slot key buffer (new values)
    k_old: jax.Array,  # [H, C, dh] old values
    vc_new: jax.Array,  # [H, C, Q] value·codebook products (new)
    vc_old: jax.Array,  # [H, C, Q]
    mask: jax.Array,  # [n, C] {0,1}: col gating & causal & row_valid & ~dirty
    T_base: jax.Array,  # [n, H, Q] scores with dirty rows pre-recomputed
    counts: jax.Array,  # [n] f32 attended-column counts (clamped >= 1)
    vq_bias: jax.Array,  # [hq, Q]
    *,
    heads_per_vq: int,
    block_r: int = 128,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (T_all [n, H, Q] f32, codes [n, hq] int32).

    The kernel runs head-major (q and T transposed to [H, n, *]) over a
    (row-block, vq-head) grid; ``block_r`` must be a multiple of 8 for the
    compiled TPU kernel. Under ``jax.vmap`` the pallas batching rule adds
    a leading document axis to the grid (the engine's batched route)."""
    n, H, dh = q.shape
    C = k_new.shape[1]
    Q = vc_new.shape[-1]
    g = heads_per_vq
    hq = H // g
    scale = dh ** -0.5
    q_t = jnp.swapaxes(q, 0, 1)  # [H, n, dh]
    tb_t = jnp.swapaxes(T_base, 0, 1)  # [H, n, Q]
    counts = counts.astype(jnp.float32).reshape(n, 1)
    pad = (-n) % block_r
    if pad:
        q_t = jnp.pad(q_t, ((0, 0), (0, pad), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0)))
        tb_t = jnp.pad(tb_t, ((0, 0), (0, pad), (0, 0)))
        # pad counts with 1 so the padded rows' score divide stays finite
        counts = jnp.pad(counts, ((0, pad), (0, 0)), constant_values=1.0)
    Np = n + pad
    T_t, codes = pl.pallas_call(
        functools.partial(_kernel, scale=scale, g=g),
        grid=(Np // block_r, hq),
        in_specs=[
            pl.BlockSpec((g, block_r, dh), lambda i, h: (h, i, 0)),
            pl.BlockSpec((g, C, dh), lambda i, h: (h, 0, 0)),
            pl.BlockSpec((g, C, dh), lambda i, h: (h, 0, 0)),
            pl.BlockSpec((g, C, Q), lambda i, h: (h, 0, 0)),
            pl.BlockSpec((g, C, Q), lambda i, h: (h, 0, 0)),
            pl.BlockSpec((block_r, C), lambda i, h: (i, 0)),
            pl.BlockSpec((g, block_r, Q), lambda i, h: (h, i, 0)),
            pl.BlockSpec((block_r, 1), lambda i, h: (i, 0)),
            pl.BlockSpec((1, 1, Q), lambda i, h: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((g, block_r, Q), lambda i, h: (h, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda i, h: (h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, Np, Q), jnp.float32),
            jax.ShapeDtypeStruct((hq, Np, 1), jnp.int32),
        ],
        interpret=interpret,
    )(q_t, k_new, k_old, vc_new, vc_old, mask, tb_t, counts,
      vq_bias.reshape(hq, 1, Q))
    return jnp.swapaxes(T_t[:, :n], 0, 1), codes[:, :n, 0].T


@functools.partial(jax.jit,
                   static_argnames=("heads_per_vq", "block_r", "interpret"))
def fused_step_kernel_batched(
    q: jax.Array,  # [B, n, H, dh]
    k_new: jax.Array,  # [B, H, C, dh]
    k_old: jax.Array,  # [B, H, C, dh]
    vc_new: jax.Array,  # [B, H, C, Q]
    vc_old: jax.Array,  # [B, H, C, Q]
    mask: jax.Array,  # [B, n, C]
    T_base: jax.Array,  # [B, n, H, Q]
    counts: jax.Array,  # [B, n]
    vq_bias: jax.Array,  # [hq, Q] (shared across the batch)
    *,
    heads_per_vq: int,
    block_r: int = 128,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Batched-serving variant: the pallas batching rule turns the
    per-document grid into a (document, row-block, vq-head) grid, so B
    documents' whole edit steps run as one ``pallas_call`` per layer. The
    vq_bias block is batch-invariant.
    Returns (T_all [B, n, H, Q] f32, codes [B, n, hq] int32)."""
    one = functools.partial(fused_step_kernel, heads_per_vq=heads_per_vq,
                            block_r=block_r, interpret=interpret)
    return jax.vmap(one, in_axes=(0,) * 8 + (None,))(
        q, k_new, k_old, vc_new, vc_old, mask, T_base, counts, vq_bias)


def _gate_kernel(xn_ref, xo_ref, keep_ref, *, threshold: float):
    # xn/xo: [BR, d]; keep: [BR, 1] int32 {0, 1}
    diff = jnp.max(jnp.abs(xn_ref[...].astype(jnp.float32)
                           - xo_ref[...].astype(jnp.float32)),
                   axis=-1, keepdims=True)  # [BR, 1]
    keep_ref[...] = (diff > threshold).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("threshold", "block_r", "interpret"))
def delta_gate_kernel(
    x_new: jax.Array,  # [r, d] freshly recomputed next-layer rows
    x_old: jax.Array,  # [r, d] the rows' last-TRANSMITTED values
    *,
    threshold: float,
    block_r: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """Per-row sigma-delta gate: ``keep[i] = max_d |x_new[i] − x_old[i]| >
    threshold`` (DESIGN.md §10). Returns ``keep [r] bool``.

    ``threshold`` is a compile-time constant — engines carry one Python
    float per instance, so the jit key matches the engine's identity key.
    L∞ and the strict compare are order-insensitive (max is associative and
    exact), so this kernel, its interpret-mode run and the inline jnp
    expression all produce bitwise-identical keep bits."""
    r, d = x_new.shape
    pad = (-r) % block_r
    if pad:
        # padded rows diff zero-against-zero: 0 > threshold is False, and
        # the slice below drops them anyway
        x_new = jnp.pad(x_new, ((0, pad), (0, 0)))
        x_old = jnp.pad(x_old, ((0, pad), (0, 0)))
    Rp = r + pad
    keep = pl.pallas_call(
        functools.partial(_gate_kernel, threshold=float(threshold)),
        grid=(Rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        interpret=interpret,
    )(x_new, x_old)
    return keep[:r, 0].astype(bool)
