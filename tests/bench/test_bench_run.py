"""The harness end to end on the CPU at a small size, with the look for a
chip skipped: a sound run comes out correct, and a run whose timed path is
broken underneath comes out not correct, once for each fault the cells can
have. Also: without a TPU the command exits non-zero and prints nothing."""
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "bench"))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import run  # noqa: E402

WORKLOAD = "opt125m.revise"
SEED = 2 ** 33 + 12345  # wider than 32 bits
MODEL = dict(name="bench-test", n_layers=2, d_model=64, n_heads=4, d_ff=128,
             vocab=512, max_seq=128, pos_pool=2048, vq_heads=2,
             codebook_size=16)
SERVING = dict(edit_capacity=4, row_capacity=8, max_batch=2,
               capacity_class_step=4, delta_threshold=0.0,
               max_batch_delay_ms=10.0, bucket_docs=2)
# the open-loop typing mix with subscriptions: its files are in bench/, and
# these are the end-to-end metrics such a cell reports
TYPING_METRICS = [{"name": n, "unit": u} for n, u in (
    ("setup_s", "s"), ("edit_ack_p95_ms", "ms"), ("suggest_p95_ms", "ms"))]


def small_cell(loop: str) -> SimpleNamespace:
    """A cell's mix and limits at a size the CPU can serve: the revise cell
    (closed loop), or the typing mix (open loop, subscriptions)."""
    c = run.resolve(WORKLOAD)
    mix, limits, e2e, cell = c.mix, c.limits, c.end_to_end, c.cell
    if loop == "open":
        mix = run.load_json(BENCH, "traffic", "typing_suggest.json")
        limits = run.load_json(BENCH, "limits", "opt125m.typing_suggest.json")
        e2e, cell = TYPING_METRICS, {"name": "typing", "chips": 1}
    mix = dict(mix, sessions=3, doc_len=[20, 40], warmup_s=1.0,
               max_doc_len=64)
    if loop == "open":
        mix.update(rate_edits_per_s=15.0, burst_gap_ms=100.0,
                   subscribe_tokens=4)
    return SimpleNamespace(cell=cell, config={"model": MODEL,
                                              "serving": SERVING},
                           root=ROOT, module=c.module, mix=mix,
                           limits=limits, end_to_end=e2e, per_layer=[])


@pytest.fixture(scope="module")
def dirs(tmp_path_factory):
    base = tmp_path_factory.mktemp("bench")
    return str(base / "cache"), str(base / "trace")


def serve(c, dirs, seconds=1.5):
    return run.run_cell(c, SEED, seconds, False, require_chip=False,
                        cache_dir=dirs[0], trace_dir=dirs[1],
                        t_start=time.perf_counter())


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(dirs, loop):
    res = serve(small_cell(loop), dirs)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["info"]["compiles_in_window"] == 0
    names = [m["name"] for m in small_cell(loop).end_to_end]
    assert sorted(res["metrics"]) == sorted(names)
    line = json.loads(json.dumps({k: v for k, v in res.items()
                                  if k != "info"}))
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert all(set(c) == {"value", "limit"} for c in line["checks"].values())


@pytest.mark.parametrize("loop,fault", [
    ("open", "state_unchanged"), ("closed", "half_batch"),
    ("closed", "edit_token_altered"), ("open", "token_altered"),
    ("closed", "patch_negated"), ("closed", "patch_skipped")])
def test_broken_timed_path_is_not_correct(dirs, monkeypatch, loop, fault):
    faults.FAULTS[fault](monkeypatch.setattr)
    res = serve(small_cell(loop), dirs)
    assert not res["correct"], res["checks"]


def test_without_a_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         WORKLOAD, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
