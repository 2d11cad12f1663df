"""Jit'd public wrapper for the fused incremental edit step.

The engine hands this the fully-folded per-(row, column) mask (changed
columns & causal order & row validity & dirty-row exclusion), the score
buffer with the dirty rows' full recompute already scattered in, and the
per-row attended-column counts; the kernel does the rest in one launch per
layer. Off a TPU backend the kernel runs in interpret mode (the same
math, the kernel body executed by XLA) so the whole stack runs on CPU; on a
TPU it always compiles (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.fused_step.fused_step import fused_step_kernel


def fused_patch_assign(q, k_new, k_old, vc_new, vc_old, mask, T_base, counts,
                       vq_bias, *, heads_per_vq: int, block_r: int = 128):
    """q: [n, H, dh]; k_*: [H, C, dh]; vc_*: [H, C, Q]; mask: [n, C];
    T_base: [n, H, Q]; counts: [n]; vq_bias: [hq, Q].
    Returns (T_all [n, H, Q] f32, codes [n, hq] int32) where
    ``T_all = T_base + ΔT`` (masked old-minus/new-plus column patch) and
    ``codes`` re-quantizes T_all in score space — one kernel launch.

    The mask must already fold EVERY gate: live-column occupancy, causal
    position order, row validity, and a zero row for every dirty row whose
    ``T_base`` entry holds a fresh full recompute (the patch must not touch
    those)."""
    return fused_step_kernel(
        q, k_new, k_old, vc_new, vc_old, mask.astype(jnp.float32), T_base,
        counts, vq_bias, heads_per_vq=heads_per_vq, block_r=block_r,
        interpret=interpret_mode(),
    )


def fused_patch_assign_batched(q, k_new, k_old, vc_new, vc_old, mask, T_base,
                               counts, vq_bias, *, heads_per_vq: int,
                               block_r: int = 128):
    """Batched serving: every per-document argument gains a leading [B]
    axis (vq_bias stays shared) and the grid gains a batch dimension.
    Returns (T_all [B, n, H, Q] f32, codes [B, n, hq] int32).

    Direct entry point for callers holding stacked buffers; the vmapped
    engine route (``BatchedJitEngine`` with ``use_fused_kernel=True``)
    reaches the same batched grid through the pallas batching rule applied
    to the unbatched ``fused_patch_assign``."""
    from repro.kernels.fused_step.fused_step import fused_step_kernel_batched

    return fused_step_kernel_batched(
        q, k_new, k_old, vc_new, vc_old, mask.astype(jnp.float32), T_base,
        counts, vq_bias, heads_per_vq=heads_per_vq, block_r=block_r,
        interpret=interpret_mode(),
    )


def delta_gate(x_new, x_old, threshold: float, *, block_r: int = 128):
    """Sigma-delta propagation gate (DESIGN.md §10): per-row L∞ change
    ``max_d |x_new − x_old|`` compared strictly against ``threshold``.
    x_new/x_old: [r, d]; returns keep [r] bool — True means the row drifted
    past the threshold from its last-transmitted value and must propagate.

    The engine feeds the keep bits into the NEXT layer's folded mask (the
    thresholded-gating mode of the fused step), so suppression costs one
    small extra launch per layer and the fused patch body is unchanged.
    Bitwise-equal to ``delta_gate_ref`` on every shape: max and > are
    order-insensitive, unlike the ΔT accumulation."""
    from repro.kernels.fused_step.fused_step import delta_gate_kernel

    return delta_gate_kernel(x_new, x_old, threshold=float(threshold),
                             block_r=block_r, interpret=interpret_mode())
