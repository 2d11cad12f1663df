"""Jit'd public wrapper for the gated σ-attention kernel.

Accepts the model-layout tensors [b, n, H, dh] (GQA repeat applied here) and
returns [b, n, H*dh], matching ``repro.models.attention.full_attention`` with
``softmax=False``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.gated_attention.gated_attention import gated_attention_kernel


def gated_attention(
    q: jax.Array,  # [b, nq, H, dh]
    k: jax.Array,  # [b, nk, Hkv, dh]
    v: jax.Array,  # [b, nk, Hkv, dh]
    *,
    block_q: int = 256,
    block_k: int = 256,
) -> jax.Array:
    b, nq, H, dh = q.shape
    Hkv = k.shape[2]
    rep = H // Hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    fold = lambda a: jnp.moveaxis(a, 2, 1).reshape(b * H, a.shape[1], a.shape[-1])
    out = gated_attention_kernel(
        fold(q), fold(k), fold(v),
        block_q=block_q, block_k=block_k, interpret=interpret_mode(),
    )  # [b*H, nq, dh]
    out = out.reshape(b, H, nq, dh)
    return jnp.moveaxis(out, 1, 2).reshape(b, nq, H * dh)
