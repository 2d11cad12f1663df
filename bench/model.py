"""The benchmark's own model: random weights made from the seed, and the
plain reference forward pass that decides ``correct``.

Nothing here imports the program. ``make_params`` builds, in ONE jitted call
on the device, the parameter pytree in the layout the program's
``BatchServer`` reads (the VQ-OPT block: pre-LN, sigma attention, a
multi-head vector quantiser on the attention output, GELU MLP, sampled
absolute positions, tied embeddings). ``forward`` is that block written
out in straightforward ``jax.numpy``: one document, sequence order, every
row recomputed, no cache, no kernel, no incremental state. ``each_layer``
runs every layer on its own from given inputs, for the check to take the
served state apart layer by layer.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


class Codebook(NamedTuple):
    """The quantiser's parameters: ``codebook`` [vq_heads, codes, d/vq_heads]."""

    codebook: jax.Array


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seeds are non-negative")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(model: dict, seed: int) -> dict:
    """Random weights of ``model`` (a configuration file's ``model`` group),
    made on the default device in one jitted call, float32."""
    L, d, H = model["n_layers"], model["d_model"], model["n_heads"]
    F, V, P = model["d_ff"], model["vocab"], model["pos_pool"]
    hq, Q = model["vq_heads"], model["codebook_size"]

    def build(key):
        ks = jax.random.split(key, 10)
        normal = lambda k, shape, s: jax.random.normal(k, shape, jnp.float32) * s
        layers = {
            "norm1": {"scale": jnp.ones((L, d)), "bias": jnp.zeros((L, d))},
            "norm2": {"scale": jnp.ones((L, d)), "bias": jnp.zeros((L, d))},
            "mixer": {
                "wq": normal(ks[0], (L, d, d), d ** -0.5),
                "wk": normal(ks[1], (L, d, d), d ** -0.5),
                "wv": normal(ks[2], (L, d, d), d ** -0.5),
                "wo": normal(ks[3], (L, d, d), d ** -0.5),
                "bq": jnp.zeros((L, d)), "bk": jnp.zeros((L, d)),
                "bv": jnp.zeros((L, d)), "bo": jnp.zeros((L, d)),
                "vq": Codebook(normal(ks[4], (L, hq, Q, d // hq), 0.5)),
            },
            "ffn": {
                "w_up": normal(ks[5], (L, d, F), d ** -0.5),
                "b_up": jnp.zeros((L, F)),
                "w_down": normal(ks[6], (L, F, d), F ** -0.5),
                "b_down": jnp.zeros((L, d)),
            },
        }
        return {
            "embed": {"tok": normal(ks[7], (V, d), 0.02),
                      "pos": normal(ks[8], (P, d), 0.02)},
            "stages": [(layers,)],
            "final_norm": {"scale": jnp.ones((d,)), "bias": jnp.zeros((d,))},
        }

    params = jax.jit(build)(key_from_seed(seed))
    return jax.block_until_ready(params)


# ------------------------------------------------------------------ reference


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


def _ln(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def _causal(valid):
    """Sequence-order causal mask over the real columns, and per row the
    number of columns it attends."""
    idx = jnp.arange(valid.shape[0])
    causal = ((idx[None, :] <= idx[:, None]) & valid[None, :]).astype(
        jnp.float32)
    return causal, jnp.maximum(causal.sum(-1), 1.0)


def _scores(lp, x, causal, count, n_heads: int, mm):
    """A layer's quantiser scores [N, vq_heads, codes]: sigma attention
    (``gelu(q.k / sqrt(dh))`` over the causal real columns, divided by the
    number of them), then ``o.c - |c|^2 / 2`` per chunk of its output."""
    mix = lp["mixer"]
    N, d = x.shape
    dh = d // n_heads
    h = _ln(x, lp["norm1"])
    q = (mm("nd,de->ne", h, mix["wq"]) + mix["bq"]).reshape(N, n_heads, dh)
    k = (mm("nd,de->ne", h, mix["wk"]) + mix["bk"]).reshape(N, n_heads, dh)
    v = (mm("nd,de->ne", h, mix["wv"]) + mix["bv"]).reshape(N, n_heads, dh)
    w = _gelu(mm("nhe,mhe->hnm", q, k) * dh ** -0.5) * causal[None]
    o = mm("hnm,mhe->nhe", w, v).reshape(N, d) / count[:, None]
    cb = mix["vq"].codebook  # [hq, Q, d/hq]
    hq = cb.shape[0]
    return (mm("nhe,hqe->nhq", o.reshape(N, hq, d // hq), cb)
            - 0.5 * (cb ** 2).sum(-1)[None])


def _finish(lp, x, code, mm):
    """The rest of a layer, row by row, given its codes [N, vq_heads]: the
    quantised attention output through ``wo``, then the MLP."""
    mix = lp["mixer"]
    N, d = x.shape
    cb = mix["vq"].codebook
    oq = jnp.take_along_axis(cb[None], code[:, :, None, None], axis=2)
    x = x + mm("nd,de->ne", oq.reshape(N, d), mix["wo"]) + mix["bo"]
    h2 = _ln(x, lp["norm2"])
    ff = lp["ffn"]
    up = _gelu(mm("nd,df->nf", h2, ff["w_up"]) + ff["b_up"])
    return x + mm("nf,fd->nd", up, ff["w_down"]) + ff["b_down"]


def embed(params: dict, tokens, positions):
    emb = params["embed"]
    return emb["tok"][tokens] + emb["pos"][positions]


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def residual_stream(params: dict, tokens, positions, valid, *, n_heads: int,
                    precision: str = "highest"):
    """One document in sequence order, from scratch: the residual stream
    [L+1, N, d] before each layer and after the last, and the codes
    [L, N, vq_heads] each layer chose.

    tokens / positions: [N] int32 (position ids as served), valid: [N] bool,
    real rows first; padded rows attend nothing real and are ignored. The
    quantiser picks, per chunk of the attention output, the code of largest
    score."""
    mm = functools.partial(einsum, precision=precision)
    causal, count = _causal(valid)
    (layers,) = params["stages"][0]

    def layer(x, lp):
        code = jnp.argmax(_scores(lp, x, causal, count, n_heads, mm), -1)
        return _finish(lp, x, code, mm), (x, code)

    x, (xs, codes) = jax.lax.scan(layer, embed(params, tokens, positions),
                                  layers)
    return jnp.concatenate([xs, x[None]]), codes


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def forward(params: dict, tokens, positions, valid, *, n_heads: int,
            precision: str = "highest"):
    """Logits [N, vocab] of one document (see ``residual_stream``)."""
    xs, _ = residual_stream(params, tokens, positions, valid,
                            n_heads=n_heads, precision=precision)
    return head(params, xs[-1], precision=precision)


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def each_layer(params: dict, xs, codes, valid, *, n_heads: int,
               precision: str = "highest"):
    """Every layer run on its own from given inputs: ``xs`` [L+1, N, d] the
    residual stream before each layer and after the last, ``codes``
    [L, N, vq_heads] the codes each layer used. Returns each layer's scores
    [L, N, vq_heads, codes] from ``xs[l]``, and its output [L, N, d] from
    ``xs[l]`` and ``codes[l]``."""
    mm = functools.partial(einsum, precision=precision)
    causal, count = _causal(valid)
    (layers,) = params["stages"][0]

    def layer(args):
        lp, x, code = args
        return (_scores(lp, x, causal, count, n_heads, mm),
                _finish(lp, x, code, mm))

    return jax.lax.map(layer, (layers, xs[:-1], codes))


def head(params: dict, x, *, precision: str = "highest"):
    """Final LayerNorm and the tied output projection: [N, d] -> [N, vocab]."""
    h = _ln(x, params["final_norm"])
    return einsum("nd,vd->nv", h, params["embed"]["tok"], precision)


@jax.jit
def token_gaps(ref_logits, chosen):
    """Per row, by how much the reference's logit of ``chosen`` lies below
    the reference's best: 0 where ``chosen`` is the reference's argmax."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, chosen[:, None], axis=-1)[:, 0]
    return best - got
