"""Jit'd wrapper: gather dirty rows, run the Pallas column-patch, return ΔT.

Capacity bucketing: the dirty-column buffers come in power-of-two capacities
(slots beyond the actual edit count are masked out), so every bucket size is
a distinct static compile — the standard serving-system bucketing pattern.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.incr_patch.incr_patch import incr_patch_kernel


def bucket_capacity(n: int, minimum: int = 8) -> int:
    from repro.common.bucketing import next_pow2

    return next_pow2(n, minimum)


def incr_patch(q, k_new, k_old, vc_new, vc_old, mask, *, row_valid=None,
               block_r: int = 128):
    """q: [R, H, dh]; k_*: [H, C, dh]; vc_*: [H, C, Q]; mask: [R, C] bool.
    Returns ΔT [R, H, Q] f32 = new-contribution − old-contribution.

    ``row_valid`` ([R] bool/float, optional) is the slot-buffer valid-row
    mask: rows whose slot is free or deleted receive a zero patch. It is
    folded into the per-(row, column) mask before the kernel launch, so the
    kernel body (and its compiled shape) is unchanged."""
    mask = mask.astype(jnp.float32)
    if row_valid is not None:
        mask = mask * row_valid.astype(jnp.float32)[:, None]
    return incr_patch_kernel(
        q, k_new, k_old, vc_new, vc_old, mask,
        block_r=block_r, interpret=interpret_mode(),
    )


def incr_patch_batched(q, k_new, k_old, vc_new, vc_old, mask, *,
                       row_valid=None, block_r: int = 128):
    """Batched serving: every argument gains a leading document axis
    (q: [B, R, H, dh]; k_*: [B, H, C, dh]; vc_*: [B, H, C, Q];
    mask: [B, R, C]; row_valid: [B, R]) and the kernel grid gains a batch
    dimension. Returns ΔT [B, R, H, Q] f32.

    This is the *direct* entry point for callers that already hold stacked
    per-document buffers (TPU serving loops built without vmap). The vmapped
    engine route (``BatchedJitEngine`` with ``use_patch_kernel=True``)
    reaches the same batched grid through the pallas batching rule applied
    to the unbatched ``incr_patch``; both are parity-tested per document."""
    from repro.kernels.incr_patch.incr_patch import incr_patch_kernel_batched

    mask = mask.astype(jnp.float32)
    if row_valid is not None:
        mask = mask * row_valid.astype(jnp.float32)[:, :, None]
    return incr_patch_kernel_batched(
        q, k_new, k_old, vc_new, vc_old, mask,
        block_r=block_r, interpret=interpret_mode(),
    )
