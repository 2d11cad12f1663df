"""The benchmark's weights and reference: the weights fit the program's
parameter layout, the reference computes what the program's own
from-scratch forward computes, and the control's ``high`` matmul is the
three-pass bfloat16 product."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "bench"))

import run  # noqa: E402
from models import common  # noqa: E402

bm = run.reference(run.load_json(run.ROOT, "bench", "configs",
                                 "vq-opt-125m.json")["reference"])

MODEL = dict(name="bench-test", n_layers=2, d_model=64, n_heads=4, d_ff=128,
             vocab=512, max_seq=128, pos_pool=2048, vq_heads=2,
             codebook_size=16)


@pytest.fixture(scope="module")
def params():
    return bm.make_params(MODEL, 2 ** 40 + 3)


def test_weights_fit_the_programs_layout(params):
    from repro.models import transformer as T

    cfg = bm.arch_config(MODEL)
    ref = T.init_params(jax.random.PRNGKey(0), cfg)
    shapes = lambda p: sorted(tuple(a.shape) for a in jax.tree.leaves(p))
    assert shapes(params) == shapes(ref)
    assert set(params) == set(ref)
    (mine,), (theirs,) = params["stages"][0], ref["stages"][0]
    assert set(mine) == set(theirs)
    assert set(mine["mixer"]) == set(theirs["mixer"])


def test_seeds_make_distinct_weights(params):
    again = bm.make_params(MODEL, 2 ** 40 + 3)
    other = bm.make_params(MODEL, 3)
    assert np.array_equal(params["embed"]["tok"], again["embed"]["tok"])
    assert not np.array_equal(params["embed"]["tok"], other["embed"]["tok"])


def test_reference_matches_the_programs_forward(params):
    from repro.models import transformer as T

    cfg = bm.arch_config(MODEL)
    rng = np.random.default_rng(0)
    n = 40
    toks = rng.integers(0, MODEL["vocab"], n).astype(np.int32)
    pos = np.sort(rng.choice(MODEL["pos_pool"], n, replace=False)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        theirs, _ = T.forward(params, cfg, jnp.asarray(toks)[None],
                              jnp.asarray(pos)[None])
    N = 48  # padded rows past the real ones change nothing
    t = np.zeros(N, np.int32)
    t[:n] = toks
    p = np.zeros(N, np.int32)
    p[:n] = pos
    mine = bm.forward(params, MODEL, jnp.asarray(t), jnp.asarray(p),
                      jnp.arange(N) < n)
    np.testing.assert_allclose(np.asarray(mine)[:n], np.asarray(theirs)[0],
                               atol=1e-4, rtol=1e-4)
    gaps = common.token_gaps(mine[:n], jnp.argmax(theirs[0], -1))
    assert float(gaps.max()) == 0.0


def test_high_is_three_bf16_passes():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    exact = common.einsum("ij,jk->ik", a, b, "highest")
    high = common.einsum("ij,jk->ik", a, b, "high")
    err = float(jnp.max(jnp.abs(high - exact)))
    assert 0 < err < 1e-3
    # on operands that bfloat16 holds exactly the low parts vanish
    a16 = a.astype(jnp.bfloat16).astype(jnp.float32)
    b16 = b.astype(jnp.bfloat16).astype(jnp.float32)
    np.testing.assert_allclose(common.einsum("ij,jk->ik", a16, b16, "high"),
                               common.einsum("ij,jk->ik", a16, b16, "highest"),
                               rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        common.einsum("ij,jk->ik", a, b, "default")
