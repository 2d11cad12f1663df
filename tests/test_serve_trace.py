"""Phase spans and time counters of the serving program (serving/trace.py).

An ``AsyncBatchServer`` serves a few rounds on the smoke config with a row
capacity small enough to overflow and a position pool small enough to
defragment, under a CPU profiler trace. Checked:

1. the five front-end counters partition the scheduler thread's time
   between two barriers, within 5%;
2. the ``serve.batch.*`` counters sum to within 5% of the front end's
   ``flush_ns``, never above it, and the suggestion phases to at most the
   refresh time;
3. in the trace, read with the benchmark's ``xplane.load``, every batch span
   nests inside a ``serve.async.flush`` span; the front end's spans carry
   its ``round``, the batch spans their ``step()`` call's ``step`` and the
   dispatch spans their ``dispatch`` id;
4. the jitted ``stack_states`` / ``unstack_state`` are bitwise equal to the
   per-leaf tree maps, with the same placement, on one device and on four.
"""
import glob
import os
import subprocess
import sys
import textwrap
import time
import warnings
from dataclasses import asdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vq_opt_125m import smoke_config
from repro.models import transformer as T
from repro.serving.async_server import AsyncBatchServer
from repro.serving.batch_engine import stack_states, unstack_state
from repro.serving.batch_server import BatchServer
from repro.serving.jit_engine import JitState

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))

import xplane  # noqa: E402

WAIT = 300.0
FRONT = ("idle_ns", "batching_ns", "admit_ns", "flush_ns", "deliver_ns")
BATCH = ("take_ns", "stack_ns", "launch_ns", "sync_ns", "adopt_ns",
         "reingest_ns", "refresh_ns")
SUGGEST = ("export_ns", "prefill_ns", "decode_ns")
DISPATCH = ("serve.batch.stack", "serve.batch.launch", "serve.batch.sync",
            "serve.batch.adopt")


def _delta(after: dict, before: dict, keys) -> int:
    return sum(after[k] - before[k] for k in keys)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Opens and a subscription up to a first barrier, then traced rounds up
    to the second: inserts at one position of a document in a 64-id pool
    (defrags), replaces in the subscribed document (refreshes), row
    capacity 4 (overflows)."""
    cfg = smoke_config(vqt=True)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    srv = BatchServer(params, cfg, edit_capacity=4, row_capacity=4,
                      max_batch=2, min_doc_capacity=16, pos_pool=64)
    rng = np.random.default_rng(7)
    asrv = AsyncBatchServer(srv, max_batch_delay_ms=3.0)
    asrv.open_document("d", list(rng.integers(0, cfg.vocab, 8)))
    asrv.open_document("e", list(rng.integers(0, cfg.vocab, 12)))
    asrv.subscribe("e", 3)
    asrv.flush(WAIT)
    before = (asdict(asrv.stats), asdict(srv.stats),
              asdict(srv.suggest_stats))
    t0 = time.perf_counter_ns()
    logdir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(logdir)
    try:
        for _ in range(8):
            asrv.submit_insert("d", 3, int(rng.integers(cfg.vocab)))
            asrv.submit_replace("e", int(rng.integers(12)),
                                int(rng.integers(cfg.vocab)))
            asrv.flush(WAIT)
        asrv.close(WAIT)  # the second barrier: drains, then stops
        wall = time.perf_counter_ns() - t0
    finally:
        jax.profiler.stop_trace()
    after = (asdict(asrv.stats), asdict(srv.stats),
             asdict(srv.suggest_stats))
    assert srv.stats.defrags >= 1 and srv.stats.overflows >= 1
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return dict(before=before, after=after, wall=wall, path=path)


def test_front_end_phases_partition_the_scheduler_thread(served):
    front = _delta(served["after"][0], served["before"][0], FRONT)
    assert abs(front - served["wall"]) <= 0.05 * served["wall"]
    assert served["after"][0]["queue_wait_ns"] > served["before"][0][
        "queue_wait_ns"]


def test_batch_phases_sum_to_the_flush(served):
    flush = _delta(served["after"][0], served["before"][0], ("flush_ns",))
    batch = _delta(served["after"][1], served["before"][1], BATCH)
    assert 0.95 * flush <= batch <= flush
    for k in BATCH:  # every phase ran in the traced rounds
        assert served["after"][1][k] > served["before"][1][k], k
    suggest = _delta(served["after"][2], served["before"][2], SUGGEST)
    refresh = _delta(served["after"][1], served["before"][1],
                     ("refresh_ns",))
    assert 0 < suggest <= refresh


def _serve_spans(path):
    """(name, start, end, stats) of every ``serve.*`` event in the trace."""
    from jax.profiler import ProfileData

    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def test_batch_spans_nest_in_the_flush_and_carry_ids(served):
    tr = xplane.load(served["path"], device_plane=r"^/host:CPU$",
                     ops_line=r"^python$", span_prefix=("bench.", "serve."))
    names = {s.name for s in tr.spans}
    assert {"serve.async.idle", "serve.async.admit", "serve.async.flush",
            "serve.async.deliver", "serve.batch.take", "serve.batch.defrag",
            "serve.batch.reingest", "serve.batch.refresh",
            "serve.suggest.prefill", *DISPATCH} <= names
    for n in names:
        assert not any(w in n for w in ("bench.", "fused_step",
                                        "_batch_apply_edits_local")), n
    flushes = [s for s in tr.spans if s.name == "serve.async.flush"]
    for s in tr.spans:
        if s.name.startswith("serve.batch."):
            assert any(f.start <= s.start and s.end <= f.end
                       for f in flushes), s

    spans = _serve_spans(served["path"])
    assert all("round" in st for n, _, _, st in spans
               if n.startswith("serve.async."))
    assert all("step" in st and "round" not in st for n, _, _, st in spans
               if n.startswith("serve.batch."))
    per_dispatch: dict = {}
    for n, start, end, st in spans:
        if n in DISPATCH:
            assert {"step", "dispatch", "docs", "R"} <= set(st)
            per_dispatch.setdefault(st["dispatch"], []).append(
                (start, n, st["step"]))
    assert len(per_dispatch) >= 6
    for d, phases in per_dispatch.items():
        phases.sort()
        assert [n for _, n, _ in phases] == list(DISPATCH), d
        assert len({r for _, _, r in phases}) == 1
    ids = sorted(per_dispatch)
    assert ids == list(range(ids[0], ids[0] + len(ids)))
    steps = [per_dispatch[d][0][2] for d in ids]
    assert steps == sorted(steps)
    rounds = [st["round"] for n, _, _, st in sorted(spans, key=lambda x: x[1])
              if n == "serve.async.flush"]
    assert rounds == sorted(rounds) and len(set(rounds)) == len(rounds)


def _states(rng, B, n=16, L=2, d=8, H=2, dh=4, Q=4, hq=2):
    def one():
        f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
        return JitState(
            tokens=jnp.asarray(rng.integers(0, 99, n), jnp.int32),
            positions=jnp.asarray(rng.permutation(n), jnp.int32),
            valid=jnp.asarray(rng.random(n) < 0.7),
            n_real=jnp.int32(11), x=f(L + 1, n, d), q=f(L, n, H, dh),
            k=f(L, n, H, dh), v=f(L, n, H, dh), vc=f(L, n, H, Q),
            T=f(L, n, H, Q),
            codes=jnp.asarray(rng.integers(0, Q, (L, n, hq)), jnp.int32))
    return [one() for _ in range(B)]


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.sharding == y.sharding
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_jitted_stack_unstack_bitwise_on_one_device():
    states = _states(np.random.default_rng(3), 4)
    stacked = stack_states(states)
    _assert_bitwise(stacked, jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *states))
    for b in range(4):
        _assert_bitwise(unstack_state(stacked, b),
                        jax.tree.map(lambda x: x[b], stacked))


def test_jitted_stack_unstack_bitwise_on_four_devices():
    """The per-leaf versions and the jitted ones under a 4-device serving
    mesh, where the edit step's output is sharded on the batch axis."""
    code = textwrap.dedent(
        """
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, os.path.join({tests!r}))
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.batch_engine import stack_states, unstack_state
        from test_serve_trace import _assert_bitwise, _states

        assert jax.device_count() == 4
        mesh = make_serving_mesh()
        batched = jax.device_put(
            jax.tree.map(lambda *xs: jnp.stack(xs),
                         *_states(np.random.default_rng(5), 4)),
            NamedSharding(mesh, P("data")))
        singles = [unstack_state(batched, b) for b in range(4)]
        for b, s in enumerate(singles):
            _assert_bitwise(s, jax.tree.map(lambda x: x[b], batched))
        _assert_bitwise(stack_states(singles),
                        jax.tree.map(lambda *xs: jnp.stack(xs), *singles))
        print("PLACED-OK")
        """
    ).format(tests=os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "PLACED-OK" in p.stdout
