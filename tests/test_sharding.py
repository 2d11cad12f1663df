"""Sharding rules + a subprocess mini dry-run (8 fake devices).

The full 512-device matrix runs via ``python -m repro.launch.dryrun --all``;
here we verify the machinery end-to-end at a tractable size. The subprocess
is required because the device-count override must happen before jax init.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import all_arch_names, get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.sharding import param_shardings


@pytest.mark.parametrize("arch", all_arch_names())
def test_param_shardings_cover_all_leaves(arch):
    from functools import partial

    import jax.numpy as jnp

    from repro.models import transformer as T

    cfg = get_config(arch, smoke=True)
    mesh = make_host_mesh()
    sds = jax.eval_shape(partial(T.init_params, cfg=cfg, dtype=jnp.float32),
                         jax.random.PRNGKey(0))
    sh = param_shardings(sds, mesh)
    leaves_a = jax.tree.leaves(sds)
    leaves_b = jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(leaves_a) == len(leaves_b)
    for sd, ns in zip(leaves_a, leaves_b):
        # every sharded dim must divide (host mesh is 1x1 so trivially true;
        # the rule itself is exercised against the production mesh below)
        assert len(ns.spec) <= len(sd.shape)


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile a reduced arch on an 8-device (2,4)+(2,2,2) mesh pair in
    a subprocess with forced host devices — the real dry-run in miniature."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        from functools import partial
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed.context import use_mesh
        from repro.launch.sharding import batch_shardings, param_shardings
        from repro.models import transformer as T
        from repro.training import make_schedule, make_train_step, train_state_init

        out = {}
        for axes, shape in [(("data", "model"), (2, 4)),
                            (("pod", "data", "model"), (2, 2, 2))]:
            # Auto axes: the model code places values with
            # with_sharding_constraint (make_mesh defaults to Explicit)
            mesh = jax.make_mesh(
                shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
            cfg = get_config("deepseek-v2-236b", smoke=True)
            with use_mesh(mesh):
                state_sds = jax.eval_shape(
                    partial(train_state_init, cfg=cfg), jax.random.PRNGKey(0))
                batch_sds = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
                step = make_train_step(cfg, make_schedule(
                    peak_lr=1e-3, warmup_steps=1, total_steps=10))
                lowered = jax.jit(step, in_shardings=(
                    param_shardings(state_sds, mesh),
                    batch_shardings(batch_sds, mesh))).lower(state_sds, batch_sds)
                compiled = lowered.compile()
                ca = compiled.cost_analysis()
                out["x".join(map(str, shape))] = float(ca.get("flops", 0))
        print(json.dumps(out))
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the forced host devices are CPU devices: keep the child off the TPU
    # library, which a parent test process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["2x4"] > 0 and res["2x2x2"] > 0
