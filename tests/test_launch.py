"""Launch-layer units: HLO stats parser, input specs, full-size configs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_arch_names, get_config
from repro.launch.hlo_stats import collective_stats, top_ops_by_bytes, _shape_bytes
from repro.launch.specs import SHAPES, decode_token_specs, input_specs, shape_supported

ASSIGNED = {
    # arch: (layers, d_model, heads, kv, vocab)
    "deepseek-v2-236b": (60, 5120, 128, 128, 102400),
    "gemma3-12b": (48, 3840, 16, 8, 262144),
    "deepseek-v3-671b": (61, 7168, 128, 128, 129280),
    "internvl2-1b": (24, 896, 14, 2, 151655),
    "musicgen-large": (48, 2048, 32, 32, 2048),
    "h2o-danube-1.8b": (24, 2560, 32, 8, 32000),
    "phi4-mini-3.8b": (32, 3072, 24, 8, 200064),
    "stablelm-1.6b": (24, 2048, 32, 32, 100352),
    "hymba-1.5b": (32, 1600, 25, 5, 32001),
    "rwkv6-7b": (32, 4096, 64, 64, 65536),
}


@pytest.mark.parametrize("arch", list(ASSIGNED))
def test_full_configs_match_assignment(arch):
    cfg = get_config(arch)
    L, d, H, kv, V = ASSIGNED[arch]
    assert cfg.n_layers == L and cfg.d_model == d
    assert cfg.n_heads == H and cfg.n_kv_heads == kv and cfg.vocab == V
    assert len(cfg.layer_list()) == L
    assert cfg.source, "every config must cite its source"
    if arch.startswith("deepseek"):
        assert cfg.moe is not None and cfg.mla is not None
        assert cfg.mla.kv_lora == 512
    if arch == "deepseek-v2-236b":
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared) == (160, 6, 2)
        assert cfg.moe.d_ff_expert == 1536
    if arch == "deepseek-v3-671b":
        assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared) == (256, 8, 1)
        assert cfg.moe.d_ff_expert == 2048 and cfg.mtp
    if arch == "gemma3-12b":
        windows = [l.window for l in cfg.layer_list()]
        assert windows.count(None) == 8 and len(windows) == 48  # 5:1 local:global
    if arch == "hymba-1.5b":
        assert cfg.ssm is not None and cfg.ssm.d_state == 16
        assert sum(1 for l in cfg.layer_list() if l.window is None) == 3
    if arch == "rwkv6-7b":
        assert all(l.mixer == "rwkv6" for l in cfg.layer_list())
        assert cfg.d_ff == 14336


def test_long_context_gating():
    allowed = {a for a in ASSIGNED if shape_supported(get_config(a), SHAPES["long_500k"])[0]}
    assert allowed == {"gemma3-12b", "h2o-danube-1.8b", "hymba-1.5b", "rwkv6-7b"}


@pytest.mark.parametrize("arch", list(ASSIGNED))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = get_config(arch)
    sc = SHAPES[shape]
    if sc.kind == "decode":
        specs = decode_token_specs(cfg, sc)
        assert specs["tokens"].shape[0] == sc.global_batch
        assert specs["tokens"].shape[1] == 1
    else:
        specs = input_specs(cfg, sc)
        total = specs["tokens"].shape[1]
        if cfg.input_mode == "vlm":
            total += specs["patch_embeds"].shape[1]
            assert specs["patch_embeds"].shape[-1] == cfg.d_model
        assert total == sc.seq_len
        assert specs["tokens"].shape[0] == sc.global_batch


def test_hlo_shape_bytes():
    assert _shape_bytes("f32[2,3]") == 24
    assert _shape_bytes("bf16[4,4]{1,0}") == 32
    assert _shape_bytes("(f32[2], s32[3])") == 8 + 12
    assert _shape_bytes("pred[]") == 1


def test_collective_stats_parses_synthetic_hlo():
    hlo = """
HloModule m
  %p0 = f32[8,16]{1,0} parameter(0)
  %ar = f32[8,16]{1,0} all-reduce(%p0), replica_groups={}
  %ag.1 = f32[16,16]{1,0} all-gather(%ar), dimensions={0}
  %x = f32[8,16]{1,0} add(%p0, %ar)
"""
    st = collective_stats(hlo)
    assert st.count_by_kind["all-reduce"] == 1
    assert st.bytes_by_kind["all-reduce"] == 8 * 16 * 4
    assert st.count_by_kind["all-gather"] == 1
    assert st.bytes_by_kind["all-gather"] == 8 * 16 * 4  # operand bytes
    ops = top_ops_by_bytes(hlo, 5)
    assert any(op == "all-gather" for op, _, _ in ops)


def test_flash_q_offset_matches_suffix():
    """Streaming attention with q_offset == computing the suffix rows of the
    full attention (the decode-prefill split invariant)."""
    from repro.models.flash import streaming_attention

    key = jax.random.PRNGKey(0)
    b, n, H, dh = 1, 64, 2, 16
    q = jax.random.normal(key, (b, n, H, dh))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, n, H, dh))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, n, H, dh))
    full = streaming_attention(q, k, v, causal=True, softmax=True, kv_block=16)
    tail = streaming_attention(q[:, 48:], k, v, causal=True, softmax=True,
                               kv_block=16, q_offset=48)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, 48:]),
                               atol=1e-5)


def test_make_serving_mesh():
    """1-D serving mesh (DESIGN.md §6): device-count-agnostic default, a
    prefix of the device list on request, loud failure past the hardware."""
    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == jax.device_count()
    one = make_serving_mesh(1)
    assert one.shape["data"] == 1
    with pytest.raises(ValueError, match="devices"):
        make_serving_mesh(jax.device_count() + 1)


def test_serving_batch_sharding_prefix():
    """The serving batch sharding (the spec every sharded dispatch uses,
    via BatchedJitEngine._sharded) puts dim 0 on the mesh axis and
    replicates the rest; unknown axes are rejected."""
    from repro.launch.mesh import make_serving_mesh
    from repro.launch.sharding import serving_batch_sharding

    mesh = make_serving_mesh(1)
    s = serving_batch_sharding(mesh)
    assert tuple(s.spec) == ("data",)
    with pytest.raises(ValueError, match="no axis"):
        serving_batch_sharding(mesh, axis="nope")


def test_chip_peaks_one_table_unknown_device_raises():
    """One peak table keyed by ``device_kind``: the v5e entry is the
    published figures, and a device the table does not know is an error,
    never a default."""
    from repro.launch.roofline import chip_peaks, edit_step_roofline

    v5e = chip_peaks("TPU v5 lite")
    assert v5e["flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peak figures"):
        chip_peaks("cpu")
    meta = dict(d=256, H=4, dh=64, Q=64, hq=2)
    with pytest.raises(ValueError):
        edit_step_roofline(2, meta, 64, 8, 16, xla_flops=1.0, xla_bytes=1.0,
                           device_kind="TPU v99")
    rl = edit_step_roofline(2, meta, 64, 8, 16, xla_flops=1.0, xla_bytes=1.0,
                            device_kind="TPU v5 lite")
    assert rl.peak_flops == v5e["flops"]
