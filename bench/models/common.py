"""Arithmetic of the references that belongs to no architecture: a key from
a seed of any width, the matmul at the configuration's precision or at the
control's, and the gap of a chosen token below the reference's best."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def einsum(eq: str, a, b, precision: str):
    """``jnp.einsum`` at f32 (``"highest"``), or as the TPU's three-pass
    bfloat16 algorithm (``"high"``: the operands split into a bfloat16 high
    and low part and the low-by-low product dropped), written out so that it
    computes the same on every backend. Every partial product is exact in
    float32, so only the dropped term and the rounding of the split differ
    from ``"highest"``."""
    if precision == "highest":
        return jnp.einsum(eq, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, a_lo = _split_bf16(a)
    b_hi, b_lo = _split_bf16(b)
    e = functools.partial(jnp.einsum, eq, precision=HIGHEST)
    return e(a_hi, b_hi) + (e(a_hi, b_lo) + e(a_lo, b_hi))


@jax.jit
def token_gaps(ref_logits, chosen):
    """Per row, by how much the reference's logit of ``chosen`` lies below
    the reference's best: 0 where ``chosen`` is the reference's argmax."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got
