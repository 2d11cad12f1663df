"""A second architecture enters the benchmark as files alone: a root with
its own BENCHMARK.json, configuration, module under bench/models/, mix,
limits and metric readers is resolved and served correct on the CPU, with no
harness file changed. The module wraps the VQ-OPT block but names its
private widths otherwise, so a harness that read an OPT key would fail."""
import functools
import json
import os
import shutil
import sys
import time

BENCH = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                     "bench"))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import xplane  # noqa: E402

CELL = "renamed.revise"
SEED = 2 ** 35 + 777
MODULE = '''"""The VQ-OPT block under other names for its private widths."""
from models import INTERFACE, vq_opt

CALLED = []
RENAMED = {"attn_heads": "n_heads", "ffn_width": "d_ff",
           "position_pool": "pos_pool", "codes": "codebook_size",
           "positions": "max_seq"}


def _opt(arg):
    if not (isinstance(arg, dict) and "attn_heads" in arg):
        return arg
    out = {k: v for k, v in arg.items() if k not in RENAMED}
    out.update({RENAMED[k]: arg[k] for k in RENAMED})
    return out


def _wrap(name):
    def call(*args, **kwargs):
        CALLED.append(name)
        return getattr(vq_opt, name)(*map(_opt, args), **kwargs)
    return call


for _name in INTERFACE:
    globals()[_name] = _wrap(_name)
'''
MODEL = dict(name="renamed-test", n_layers=2, d_model=64, attn_heads=4,
             ffn_width=128, vocab=512, positions=128, position_pool=2048,
             vq_heads=2, codes=16)
SERVING = dict(edit_capacity=4, row_capacity=8, max_batch=2,
               capacity_class_step=4, delta_threshold=0.0,
               max_batch_delay_ms=10.0, bucket_docs=2)
METRICS = ("setup_s", "edits_per_s", "docs_per_dispatch.revise")


def write(root, rel, obj):
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(obj if isinstance(obj, str) else json.dumps(obj))


def make_root(root: str) -> None:
    e2e = [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
            "source": "host_clock"},
           {"name": "edits_per_s", "unit": "edits/s", "better": "higher",
            "bound": 0.25, "source": "host_clock"}]
    write(root, "BENCHMARK.json", {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 30,
        "configs": [{"name": "renamed-test", "source": "test",
                     "file": "bench/configs/renamed-test.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": CELL, "config": "renamed-test",
                       "traffic": "revise_small", "chips": 1,
                       "why": "test"}],
        "end_to_end": e2e,
        "per_layer": [{"name": "docs_per_dispatch.revise", "unit": "docs",
                       "better": "higher", "source": "program_counter",
                       "layer": "scheduler", "moves": "edits_per_s",
                       "workloads": [CELL]}]})
    write(root, "bench/configs/renamed-test.json", {
        "name": "renamed-test", "source": "test", "reduced": [],
        "reference": "bench/models/renamed_opt.py", "model": MODEL,
        "serving": SERVING})
    write(root, "bench/models/renamed_opt.py", MODULE)
    mix = dict(run.load_json(BENCH, "traffic", "revise.json"), sessions=3,
               doc_len=[20, 40], warmup_s=1.0, max_doc_len=64)
    write(root, "bench/traffic/revise_small.json", mix)
    write(root, f"bench/limits/{CELL}.json",
          run.load_json(BENCH, "limits", "opt125m.revise.json"))
    os.makedirs(os.path.join(root, "bench", "metrics"))
    for name in METRICS:
        shutil.copy(os.path.join(BENCH, "metrics", name + ".py"),
                    os.path.join(root, "bench", "metrics"))


def test_second_architecture_enters_as_files(tmp_path, monkeypatch):
    root = str(tmp_path / "root")
    make_root(root)
    c = run.resolve(CELL, root=root)
    assert os.path.realpath(c.module.__file__) == os.path.realpath(
        os.path.join(root, "bench", "models", "renamed_opt.py"))
    # the CPU has no device plane: read the host thread that ran the
    # programs as the "device" line, so the traced run reaches the counts
    monkeypatch.setattr(xplane, "load", functools.partial(
        xplane.load, device_plane=r"^/host:CPU$", ops_line=r"^python$"))
    res = run.run_cell(c, SEED, 1.5, True, require_chip=False,
                       cache_dir=str(tmp_path / "cache"),
                       trace_dir=str(tmp_path / "trace"),
                       t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "docs_per_dispatch.revise" in res["metrics"]
    assert {"make_params", "arch_config", "forward",
            "edit_flops"} <= set(c.module.CALLED)
