"""The comparison that decides ``correct``.

After the window closes, each document is read back from the server: its
tokens, the position ids it was served at, the residual stream of every
real row before each layer and after the last, the VQ codes each layer
chose (all of it what the incremental edit step and its ``fused_step``
kernel left behind), and the last suggestion its stream delivered. The
program is then freed, and the reference of the configuration's
architecture (its module under ``bench/models/``) runs over each
document. The numbers compared:

* ``token_mismatch``: documents whose served tokens differ from the
  traffic generator's replay of everything sent, or whose device tokens
  differ from the served ones (exact, limit 0);
* ``stale_suggestions``: subscribed documents whose last delivered
  suggestion does not reflect every acknowledged edit (exact, limit 0);
* ``code_gap``: each layer run alone on the served residual stream before
  it; over every layer, real row and VQ head, the widest gap by which the
  reference's score of the served code lies below the reference's best.
  This reads the score totals that ``fused_step`` patches and the codes it
  re-assigns: a sound step differs only on codes tied to rounding;
* ``layer_err``: the largest difference between the served residual stream
  after each layer and that layer of the reference run on the served
  stream before it with the served codes (and between the served
  embeddings and the reference's);
* ``suggest_gap``: with each delivered suggestion appended, the widest gap
  by which the reference's logit of a suggested token lies below the
  reference's best at its position (greedy decoding);
* ``row_err_median``: the reference's whole forward pass from the tokens;
  the median over all real rows of the largest difference between the
  logits of the served final state and the reference's.

Printed, not compared: ``state_gap`` (the widest gap of the token the
served final state puts first, over every row) and ``logit_err`` (the
widest logit difference of any row). A code tied to rounding that the two
sides assign differently moves a row, and through attention the rows after
it, so these swing from seed to seed; ``code_gap`` and ``layer_err``,
taken layer by layer from the served inputs, do not compound that way.

The control (``control=True``) puts the reference itself, computed in the
TPU's three-pass bfloat16 matmul (``high``, the step below the ``highest``
that the configuration states), in the program's place: at the same
positions of the same documents and tokens it reads the gap of the code and
the token that the lower precision puts first, and the layers' outputs at
that precision.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from models.common import token_gaps


class _Static(dict):
    """A configuration's ``model`` group as a static argument of a jitted
    function."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def _code_gaps(ref_scores, chosen):
    """[L, N]: per layer and row, the widest gap over VQ heads."""
    best = ref_scores.max(-1)
    got = jnp.take_along_axis(ref_scores, chosen[..., None], axis=-1)[..., 0]
    return (best - got).max(-1)


@functools.partial(jax.jit, static_argnames=("ref", "model", "control"))
def _readings(params, toks, pos, valid, xs, codes, sugg, state_rows,
              sugg_rows, *, ref, model, control):
    n = state_rows.astype(bool)
    logits = ref.forward(params, model, toks, pos, valid,
                         precision="highest")
    scores, outs = ref.each_layer(params, model, xs, codes, n)
    x0 = ref.embed(params, model, toks, pos)
    if control:
        low = ref.forward(params, model, toks, pos, valid, precision="high")
        served_first = jnp.argmax(low, -1)
        sugg_first = served_first
        row_err = jnp.max(jnp.abs(low - logits), -1)
        low_scores, low_outs = ref.each_layer(params, model, xs, codes, n,
                                              precision="high")
        chosen = jnp.argmax(low_scores, -1)
        layer_err = jnp.max(jnp.abs(low_outs - outs), -1)
    else:
        served = ref.head(params, model, xs[-1])
        served_first = jnp.argmax(served, -1)
        sugg_first = sugg
        row_err = jnp.max(jnp.abs(served - logits), -1)
        chosen = codes
        layer_err = jnp.concatenate(
            [jnp.max(jnp.abs(xs[:1] - x0), -1),
             jnp.max(jnp.abs(xs[1:] - outs), -1)])
    rows = state_rows[None]
    code_gap = jnp.max(_code_gaps(scores, chosen) * rows)
    state_gap = jnp.max(token_gaps(logits, served_first) * state_rows)
    sugg_gap = jnp.max(token_gaps(logits, sugg_first) * sugg_rows)
    return (state_gap, sugg_gap, row_err, code_gap,
            jnp.max(layer_err * rows))


def compare(module, params, model: dict, docs: list, *, max_len: int,
            control: bool = False) -> dict:
    """Readings of ``module``'s reference (the configuration's
    architecture) over ``docs``: dicts with ``replay`` (tokens the generator
    says the document holds), ``served`` (the server's tokens), ``device``
    (the device state's tokens in position order), ``positions`` (the
    served position ids in sequence order), ``xs`` ([L+1, n, d] residual
    stream before each layer and after the last, sequence order), ``codes``
    ([L, n, vq_heads]), ``suggestion`` (last delivered tokens, or None) and
    ``stale`` (True when it missed an acknowledged edit)."""
    mism = sum(d["served"] != d["replay"] or d["device"] != d["served"]
               for d in docs)
    stale = sum(bool(d.get("stale")) for d in docs)
    worst = {"state_gap": 0.0, "suggest_gap": 0.0, "logit_err": 0.0,
             "code_gap": 0.0, "layer_err": 0.0}
    row_errs = []
    N = max_len + max(len(d["suggestion"] or ()) for d in docs)
    L, width = model["n_layers"], model["d_model"]
    for d in docs:
        n = len(d["replay"])
        if len(d["positions"]) != n:  # the served state lost or gained rows
            for k in worst:
                worst[k] = float("inf")
            continue
        sugg = list(d["suggestion"] or ())
        S = len(sugg)
        toks = np.zeros(N, np.int32)
        toks[:n + S] = d["replay"] + sugg
        pos = np.zeros(N, np.int32)
        pos[:n] = d["positions"]
        pos[n:n + S] = d["positions"][-1] + 1 + np.arange(S)
        valid = np.arange(N) < n + S
        xs = np.zeros((L + 1, N, width), np.float32)
        xs[:, :n] = d["xs"]
        codes = np.zeros((L, N, model["vq_heads"]), np.int32)
        codes[:, :n] = d["codes"]
        chosen = np.zeros(N, np.int32)
        chosen[n - 1:n - 1 + S] = sugg
        state_rows = (np.arange(N) < n).astype(np.float32)
        sugg_rows = ((np.arange(N) >= n - 1)
                     & (np.arange(N) < n - 1 + S)).astype(np.float32)
        sg, gg, rows, cg, le = _readings(
            params, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(valid),
            jnp.asarray(xs), jnp.asarray(codes), jnp.asarray(chosen),
            jnp.asarray(state_rows), jnp.asarray(sugg_rows), ref=module,
            model=_Static(model), control=control)
        for k, v in (("state_gap", sg), ("suggest_gap", gg),
                     ("code_gap", cg), ("layer_err", le)):
            worst[k] = max(worst[k], float(v))
        rows = np.asarray(rows)[:n]
        row_errs.append(rows)
        worst["logit_err"] = max(worst["logit_err"], float(rows.max()))
    median = float(np.median(np.concatenate(row_errs))) if row_errs \
        else float("inf")
    return {"token_mismatch": int(mism), "stale_suggestions": int(stale),
            **worst, "row_err_median": median}


def judge(readings: dict, limits: dict) -> tuple:
    """(correct, [[name, reading, limit], ...]) over the numbers that have
    a limit; a reading must not exceed its limit."""
    rows = [[k, readings[k], limits[k]] for k in limits]
    return all(r <= lim for _, r, lim in rows), rows
