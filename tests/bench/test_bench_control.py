"""The control: the reference itself, computed in the TPU's three-pass
bfloat16 matmul (``high``, the precision below the configuration's
``highest``), put in the program's place, must come out not correct under
each cell's limits, while a state that the reference made at ``highest``
comes out correct. Here at vq-opt-125m's widths with two of its layers, on
documents of the cells' lengths, on three seeds."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "bench")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from check import compare, judge  # noqa: E402

with open(os.path.join(BENCH, "configs", "vq-opt-125m.json")) as f:
    CONFIG = json.load(f)
MODEL = dict(CONFIG["model"], n_layers=2)
bm = run.reference(CONFIG["reference"])
MAX_LEN = 512


def documents(params, seed):
    """Two documents as a sound server would hold them: the reference's
    own residual stream and codes, with a greedy suggestion appended."""
    rng = np.random.default_rng(seed)
    docs = []
    for n in (300, 450):
        toks = [int(t) for t in rng.integers(0, MODEL["vocab"], n)]
        pos = ((np.arange(1, n + 1) * MODEL["pos_pool"]) // (n + 1)).astype(
            np.int32)
        t, p = np.zeros(MAX_LEN, np.int32), np.zeros(MAX_LEN, np.int32)
        t[:n], p[:n] = toks, pos
        xs, codes = bm.residual_stream(params, MODEL, jnp.asarray(t),
                                       jnp.asarray(p), jnp.arange(MAX_LEN) < n)
        first = int(jnp.argmax(bm.head(params, MODEL, xs[-1, n - 1:n]),
                               -1)[0])
        docs.append({"replay": toks, "served": toks, "device": toks,
                     "positions": pos, "xs": np.asarray(xs)[:, :n],
                     "codes": np.asarray(codes)[:, :n],
                     "suggestion": [first]})
    return docs


@pytest.mark.parametrize("limits_file", sorted(os.listdir(
    os.path.join(BENCH, "limits"))))
def test_control_is_not_correct(limits_file):
    with open(os.path.join(BENCH, "limits", limits_file)) as f:
        limits = json.load(f)
    for seed in (11, 12, 13):
        params = bm.make_params(MODEL, seed)
        docs = documents(params, seed)
        sound = compare(bm, params, MODEL, docs, max_len=MAX_LEN)
        correct, rows = judge(sound, limits)
        assert correct, rows
        control = compare(bm, params, MODEL, docs, max_len=MAX_LEN,
                          control=True)
        correct, rows = judge(control, limits)
        assert not correct, rows
