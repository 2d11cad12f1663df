"""The VQ-OPT block: random weights made from the seed, the plain reference
forward pass that decides ``correct``, the program's configuration of the
block, and the work an edit of it cannot do without.

The reference imports nothing of the program; ``arch_config`` alone reaches
into it, for the program's own description of the block. ``make_params``
builds, in ONE jitted call on the device, the parameter pytree in the
layout the program's ``BatchServer`` reads (the VQ-OPT block: pre-LN, sigma
attention, a multi-head vector quantiser on the attention output, GELU MLP,
sampled absolute positions, tied embeddings). ``forward`` is that block
written out in straightforward ``jax.numpy``: one document, sequence order,
every row recomputed, no cache, no kernel, no incremental state.
``each_layer`` runs every layer on its own from given inputs, for the check
to take the served state apart layer by layer. The interface is
``models.INTERFACE``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from models.common import einsum, key_from_seed

F32 = 4


class Codebook(NamedTuple):
    """The quantiser's parameters: ``codebook`` [vq_heads, codes, d/vq_heads]."""

    codebook: jax.Array


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


def _embed(params: dict, tokens, positions):
    emb = params["embed"]
    return emb["tok"][tokens] + emb["pos"][positions]


def _head(params: dict, x, precision: str):
    h = _ln(x, params["final_norm"])
    return einsum("nd,vd->nv", h, params["embed"]["tok"], precision)


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def _residual_stream(params: dict, tokens, positions, valid, *, n_heads: int,
                     precision: str):
    mm = functools.partial(einsum, precision=precision)
    causal, count = _causal(valid)
    (layers,) = params["stages"][0]

    def layer(x, lp):
        code = jnp.argmax(_scores(lp, x, causal, count, n_heads, mm), -1)
        return _finish(lp, x, code, mm), (x, code)

    x, (xs, codes) = jax.lax.scan(layer, _embed(params, tokens, positions),
                                  layers)
    return jnp.concatenate([xs, x[None]]), codes


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def _forward(params: dict, tokens, positions, valid, *, n_heads: int,
             precision: str):
    xs, _ = _residual_stream(params, tokens, positions, valid,
                             n_heads=n_heads, precision=precision)
    return _head(params, xs[-1], precision)


@functools.partial(jax.jit, static_argnames=("n_heads", "precision"))
def _each_layer(params: dict, xs, codes, valid, *, n_heads: int,
                precision: str):
    mm = functools.partial(einsum, precision=precision)
    causal, count = _causal(valid)
    (layers,) = params["stages"][0]

    def layer(args):
        lp, x, code = args
        return (_scores(lp, x, causal, count, n_heads, mm),
                _finish(lp, x, code, mm))

    return jax.lax.map(layer, (layers, xs[:-1], codes))


def embed(params: dict, model, tokens, positions):
    """The residual stream before the first layer [N, d]: token plus
    position embedding."""
    return _embed(params, tokens, positions)


def residual_stream(params: dict, model, tokens, positions, valid, *,
                    precision: str = "highest"):
    """One document in sequence order, from scratch: the residual stream
    [L+1, N, d] before each layer and after the last, and the codes
    [L, N, vq_heads] each layer chose.

    tokens / positions: [N] int32 (position ids as served), valid: [N] bool,
    real rows first; padded rows attend nothing real and are ignored. The
    quantiser picks, per chunk of the attention output, the code of largest
    score."""
    return _residual_stream(params, tokens, positions, valid,
                            n_heads=model["n_heads"], precision=precision)


def forward(params: dict, model, tokens, positions, valid, *,
            precision: str = "highest"):
    """Logits [N, vocab] of one document (see ``residual_stream``)."""
    return _forward(params, tokens, positions, valid,
                    n_heads=model["n_heads"], precision=precision)


def each_layer(params: dict, model, xs, codes, valid, *,
               precision: str = "highest"):
    """Every layer run on its own from given inputs: ``xs`` [L+1, N, d] the
    residual stream before each layer and after the last, ``codes``
    [L, N, vq_heads] the codes each layer used. Returns each layer's scores
    [L, N, vq_heads, codes] from ``xs[l]``, and its output [L, N, d] from
    ``xs[l]`` and ``codes[l]``."""
    return _each_layer(params, xs, codes, valid, n_heads=model["n_heads"],
                       precision=precision)


def head(params: dict, model, x, *, precision: str = "highest"):
    """Final LayerNorm and the tied output projection: [N, d] -> [N, vocab]."""
    return _head(params, x, precision)


# ------------------------------------------------------- the program's config


def arch_config(model: dict):
    """The program's ``ArchConfig`` for a configuration file's ``model``."""
    from repro.configs.base import LayerCfg, uniform_stages
    from repro.configs.vq_opt_125m import config

    cfg = config(vq_heads=model["vq_heads"])
    cfg = dataclasses.replace(
        cfg, name=model["name"], n_layers=model["n_layers"],
        d_model=model["d_model"], n_heads=model["n_heads"],
        n_kv_heads=model["n_heads"], d_ff=model["d_ff"],
        vocab=model["vocab"], max_seq=model["max_seq"],
        pos_pool=model["pos_pool"],
        stages=uniform_stages(LayerCfg(mixer="gqa", ffn="gelu"),
                              model["n_layers"]),
        vqt=dataclasses.replace(cfg.vqt, n_heads=model["vq_heads"],
                                codebook_size=model["codebook_size"]))
    return cfg.validate()


# ------------------------------------------------------------ work of an edit
#
# Counts are floors, taken from one edited document's real sizes: its real
# length ``n`` and its edits by kind. Per layer, with ``c_new`` replaced or
# inserted tokens and ``c_del`` deleted ones (``d`` width, ``H`` heads of
# ``dh``, ``Q`` codes per VQ head, ``F`` MLP width):
#
# * every edited token's row changes at every layer (its residual stream
#   carries the new embedding), so ``c_new`` rows are recomputed: q/k/v
#   projections ``2 d 3 H dh``, value-codebook products ``2 H dh Q``, their
#   attention over the ``n`` real columns ``2 n H dh + 2 n H Q``, and the
#   MLP ``2 * 2 d F``;
# * every other real row's accumulated scores take the edited columns: a
#   replaced token's column is subtracted and added (2 columns), an inserted
#   one added (1), a deleted one subtracted (1), each ``2 n H dh`` for the
#   scores and ``2 n H Q`` for the value accumulation.
#
# The ``fused_step`` kernel is the column patch and the re-quantisation: its
# floor is the column terms, and its bytes are the ``q`` rows it must read,
# the score totals ``T`` it reads and writes, and the codes it writes, over
# the ``n`` real rows, per layer. Rows that an edit's codes propagate to are
# not counted (the device alone knows them), so these are floors.


def _dims(model: dict):
    d, H = model["d_model"], model["n_heads"]
    return d, H, d // H, model["codebook_size"], model["d_ff"], \
        model["n_layers"]


def columns(n_repl: int, n_ins: int, n_del: int) -> int:
    """Column passes of the patch: a replace subtracts and adds."""
    return 2 * n_repl + n_ins + n_del


def kernel_flops(model: dict, n: int, n_repl: int, n_ins: int,
                 n_del: int) -> float:
    d, H, dh, Q, F, L = _dims(model)
    return float(L * columns(n_repl, n_ins, n_del)
                 * (2 * n * H * dh + 2 * n * H * Q))


def kernel_bytes(model: dict, n: int, n_repl: int, n_ins: int,
                 n_del: int) -> float:
    d, H, dh, Q, F, L = _dims(model)
    if columns(n_repl, n_ins, n_del) == 0:
        return 0.0
    hq = model["vq_heads"]
    return float(L * n * (H * dh * F32          # q rows read
                          + 2 * H * Q * F32     # T read and written
                          + hq * 4))            # codes written


def edit_flops(model: dict, n: int, n_repl: int, n_ins: int,
               n_del: int) -> float:
    """Floor of one document's edit step: row recomputes plus the patch."""
    d, H, dh, Q, F, L = _dims(model)
    c_new = n_repl + n_ins
    rows = c_new * (2 * d * 3 * H * dh + 2 * H * dh * Q
                    + 2 * n * H * dh + 2 * n * H * Q + 2 * 2 * d * F)
    return float(L * rows) + kernel_flops(model, n, n_repl, n_ins, n_del)
