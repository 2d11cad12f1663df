"""Batched decode serving step (used by the decode_32k / long_500k shapes).

``serve_step`` consumes ONE new token per sequence against per-layer KV /
recurrent-state caches of ``seq_len`` and returns next-token logits plus the
updated caches — the standard continuous-batching inner loop.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import transformer as T


def make_serve_step(cfg: ArchConfig, *, sample: bool = False, temperature: float = 1.0):
    """Returns ``serve_step(params, caches, tokens, positions, rng?) ->
    (next_tokens_or_logits, caches)``."""

    def serve_step(params, caches, tokens, positions, rng: Optional[jax.Array] = None):
        logits, caches = T.decode_step(params, cfg, tokens, caches, positions)
        if not sample:
            return logits, caches
        if temperature == 0.0:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            assert rng is not None
            nxt = jax.random.categorical(rng, logits / temperature, axis=-1)
        return nxt.astype(jnp.int32), caches

    return serve_step


def make_prefill_step(cfg: ArchConfig):
    """Returns ``prefill_step(params, caches, tokens, positions) ->
    (logits, caches)``: ``models.transformer.prefill_step`` over a chunk of
    ``m`` tokens, under a name of its own for jit and the profiler."""

    def prefill_step(params, caches, tokens, positions):
        return T.prefill_step(params, cfg, tokens, caches, positions)

    return prefill_step


def greedy_continue(step, params, caches, logits_last: jax.Array,
                    gen_positions: jax.Array,
                    on_token=None) -> tuple[jax.Array, jax.Array]:
    """The greedy continuation inner loop shared by ``greedy_decode`` and
    the suggestion engine: ``logits_last`` [b, vocab] (audio [b, cb, vocab])
    are the logits of the last consumed token; ``gen_positions`` [b, n_new]
    the continuation position ids. Runs ``n_new - 1`` decode steps (the
    first token needs none). ``on_token``, when given, is called with each
    [b, 1] token array as the loop produces it — a streaming tap (the async
    front end forwards tokens to subscribers before the continuation is
    complete); it forces a device sync per token, so leave it None on
    latency-insensitive paths. Returns (tokens [b, n_new], caches)."""
    n_new = gen_positions.shape[1]
    cur = jnp.argmax(logits_last, axis=-1).astype(jnp.int32)[:, None]
    if on_token is not None:
        on_token(np.asarray(cur))
    out = [cur]
    for i in range(1, n_new):
        logits, caches = step(params, caches, cur, gen_positions[:, i - 1 : i])
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        if on_token is not None:
            on_token(np.asarray(cur))
        out.append(cur)
    return jnp.concatenate(out, axis=1), caches


def greedy_decode(params, cfg: ArchConfig, prompt: jax.Array, n_new: int,
                  cache_len: int = 0, positions: Optional[jax.Array] = None,
                  gen_positions: Optional[jax.Array] = None):
    """Reference greedy decoding loop for tests/examples: prefill the prompt
    in ONE batched ``prefill_step`` (configs whose decode cache supports
    chunked writes — else a per-token fallback), then generate ``n_new``
    tokens. prompt: [b, n] (audio [b, n, cb]).

    ``positions`` ([b, n]) / ``gen_positions`` ([b, n_new]) override the
    default dense 0..n+n_new-1 position ids — gapped-id documents (the
    paper's sampled positional embeddings) pass their own. Returns
    (generated [b, n_new], caches)."""
    b, n = prompt.shape[:2]
    if cache_len and cache_len < n + n_new:
        # full (non-ring) caches clamp out-of-range writes: generating past
        # the cache end would silently stomp the last KV row
        raise ValueError(f"cache_len {cache_len} < prompt + n_new = {n + n_new}")
    caches = T.init_caches(cfg, b, cache_len or (n + n_new), dtype=jnp.float32)
    step = jax.jit(make_serve_step(cfg, sample=False))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    if gen_positions is None:
        gen_positions = positions[:, -1:] + 1 + jnp.arange(n_new, dtype=jnp.int32)
    if T.chunkable(cfg):
        prefill = jax.jit(make_prefill_step(cfg))
        logits, caches = prefill(params, caches, prompt, positions)
        logits = logits[:, -1:]
    else:
        for i in range(n):
            logits, caches = step(params, caches, prompt[:, i : i + 1],
                                  positions[:, i : i + 1])
    return greedy_continue(step, params, caches, logits[:, -1], gen_positions)
