"""Compile the serving path for a TPU v5e at vq-opt-125m widths, with no chip.

The TPU compiler is installed beside JAX and compiles for a *described*
topology: what it refuses — a block shape off the (8, 128) tiling rule, a
kernel over its fast-memory budget, a step that does not fit the chip — it
refuses here, at no chip time. Nothing runs, so these tests say nothing
about results or speed.

Only one process at a time may load the TPU library, and it keeps it until
it exits. So the topology is described inside a module fixture, never while
a module is imported: under pytest-xdist only the worker given this file
loads the library, and every worker collects the same tests. The kernels'
interpret choice follows the backend (the CPU here), so the tests steer the
engine to the compiled kernel themselves.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.vq_opt_125m import config, smoke_config
from repro.kernels.fused_step.fused_step import (
    delta_gate_kernel, fused_step_kernel,
)
from repro.serving.batch_engine import BatchedJitEngine
from repro.serving.jit_engine import JitIncrementalEngine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def weight_specs(cfg, sharding=None):
    """Shapes of ``_weights_from_params(params, cfg)`` — the engine's stacked
    weights — as ShapeDtypeStructs, so a full-width step compiles without
    building a full-width model."""
    L, d, H = cfg.n_layers, cfg.d_model, cfg.n_heads
    dh, F, V = cfg.resolved_head_dim, cfg.d_ff, cfg.vocab
    hq, Q = cfg.vqt.n_heads, cfg.vqt.codebook_size

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    W = dict(ln1_s=S(L, d), ln1_b=S(L, d), wq=S(L, d, H, dh), bq=S(L, H, dh),
             wk=S(L, d, H, dh), bk=S(L, H, dh), wv=S(L, d, H, dh),
             bv=S(L, H, dh), bo=S(L, d), ln2_s=S(L, d), ln2_b=S(L, d),
             w_up=S(L, d, F), b_up=S(L, F), w_down=S(L, F, d), b_down=S(L, d),
             cb_per_head=S(L, H, Q, dh), vq_bias=S(L, hq, Q),
             c_wo=S(L, hq, Q, d))
    extras = dict(tok_emb=S(V, d), pos_emb=S(cfg.pos_pool, d), fn_s=S(d),
                  fn_b=S(d), head_w=S(d, V))
    meta = dict(H=H, dh=dh, d=d, hq=hq, Q=Q, heads_per_vq=H // hq,
                scale=float(dh ** -0.5))
    return W, extras, meta


def test_weight_specs_match_the_engine():
    """The hand-written full-width shapes follow the real extraction (checked
    at smoke width, where building the model is cheap)."""
    from repro.models import transformer as T

    cfg = smoke_config(vqt=True)
    eng = JitIncrementalEngine(T.init_params(jax.random.PRNGKey(0), cfg), cfg)
    W, extras, meta = weight_specs(cfg)
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    assert shapes((W, extras)) == shapes(eng.weights[:2])
    assert meta == pytest.approx(eng.meta)


@pytest.mark.parametrize("n,C", [(256, 8), (1024, 72)])
def test_fused_step_compiles_in_the_engines_vmapped_form(one_chip, n, C):
    """vq-opt-125m widths (H 12, dh 64, Q 64, 2 vq heads), batch 8; C 72 is
    a deeper layer's column set (row bucket 64 + edit bucket 8)."""
    B, H, dh, Q, hq = 8, 12, 64, 64, 2

    def one(q, kn, ko, vn, vo, m, tb, c, bias):
        return fused_step_kernel(q, kn, ko, vn, vo, m, tb, c, bias,
                                 heads_per_vq=H // hq, interpret=False)

    S = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.vmap(one, in_axes=(0,) * 8 + (None,))).lower(
        S(B, n, H, dh), S(B, H, C, dh), S(B, H, C, dh), S(B, H, C, Q),
        S(B, H, C, Q), S(B, n, C), S(B, n, H, Q), S(B, n), S(hq, Q),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_delta_gate_compiles(one_chip):
    S = jax.ShapeDtypeStruct((64, 768), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a, b: delta_gate_kernel(
        a, b, threshold=0.5, interpret=False)).lower(S, S).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_apply_edits_step_compiles(one_chip, monkeypatch):
    """One whole 12-layer batched edit step — 8 documents of 512 slots, edit
    bucket 8, row bucket 64 — with the weights and the state as shapes. It
    runs the compiled kernel and fits one 16 GB chip."""
    import repro.kernels.fused_step.ops as fused_ops

    monkeypatch.setattr(fused_ops, "interpret_mode", lambda: False)
    cfg = config()
    B, n, C, R = 8, 512, 8, 64
    eng = BatchedJitEngine({}, cfg, edit_capacity=C, row_capacity=R,
                           use_fused_kernel=True,
                           _weights=weight_specs(cfg, one_chip))
    slots = jax.ShapeDtypeStruct((n,), jnp.int32)
    one = jax.eval_shape(eng._full_forward_impl, eng.wts, slots, slots,
                         jax.ShapeDtypeStruct((n,), bool))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (B,) + a.shape, a.dtype, sharding=one_chip), one)
    bucket = jax.ShapeDtypeStruct((B, C), jnp.int32, sharding=one_chip)
    compiled = eng._batch_apply_edits_local.jitted.lower(
        eng, eng.wts, state, bucket, bucket, bucket, bucket).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
