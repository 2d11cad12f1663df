"""Matmul precision of the serving programs.

Serving answers are held exact against from-scratch references: the
incremental engine's tokens and logits against a full forward, and a
suggestion that reuses the engine's exported k/v against a decode that
re-prefills every row. The paths order their sums differently, and each
layer ends in a VQ argmax, so any rounding difference can flip a near-tied
code and change the answer. On a TPU the default f32 matmul rounds its
operands to bf16 in one pass, which makes those differences about a
thousand times larger than f32 rounding. Every serving program therefore
traces its matmuls at f32-faithful precision (a CPU computes f32 matmuls
in full either way), and the fused kernel pins its own dots.
"""
from __future__ import annotations

import functools

import jax

SERVING_PRECISION = jax.lax.Precision.HIGHEST


def pinned_precision(fn):
    """``fn`` with every matmul it traces at ``SERVING_PRECISION``; wrap a
    function before ``jax.jit`` so the pin holds inside its trace."""

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return run
