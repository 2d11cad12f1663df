"""Jit'd public wrapper: quantize attention outputs with the Pallas kernel.

Runs in interpret mode off a TPU backend (``repro.kernels.interpret_mode``)
so the whole stack runs on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.vq_assign.vq_assign import vq_assign_kernel


def vq_assign(
    x: jax.Array,  # [..., d] attention outputs
    codebook: jax.Array,  # [hq, Q, dv] with hq*dv == d
    *,
    block_n: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Returns (idx [..., hq] int32, x_q [..., d])."""
    hq, Q, dv = codebook.shape
    *lead, d = x.shape
    assert hq * dv == d, (codebook.shape, d)
    xh = x.reshape(-1, hq, dv)
    idx, xq = vq_assign_kernel(xh, codebook, block_n=block_n,
                               interpret=interpret_mode())
    return idx.reshape(*lead, hq), xq.reshape(*lead, d).astype(x.dtype)


def vq_assign_batched(
    x: jax.Array,  # [B, N, d] a batch of documents' attention outputs
    codebook: jax.Array,  # [hq, Q, dv] with hq*dv == d
    *,
    block_n: int = 256,
) -> tuple[jax.Array, jax.Array]:
    """Batched serving: quantize B documents in one kernel launch whose grid
    has a leading batch dimension (the codebook block is batch-invariant).
    Returns (idx [B, N, hq] int32, x_q [B, N, d])."""
    from repro.kernels.vq_assign.vq_assign import vq_assign_kernel_batched

    hq, Q, dv = codebook.shape
    B, N, d = x.shape
    assert hq * dv == d, (codebook.shape, d)
    xh = x.reshape(B, N, hq, dv)
    idx, xq = vq_assign_kernel_batched(xh, codebook, block_n=block_n,
                                       interpret=interpret_mode())
    return idx, xq.reshape(B, N, d).astype(x.dtype)
