"""BENCHMARK.json is well formed, and every name in it resolves to a file:
each configuration, its architecture's module, traffic mix, limits file and
metric reader. Outside ``bench/models/`` the harness knows no architecture."""
import importlib.util
import json
import math
import os
import re
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, os.path.join(ROOT, "bench"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    for p in spec["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51


def test_check_budget_fits_24_cells(spec):
    """A full check of 24 cells: 2 + 14 runs a cell, each run_seconds + 60,
    two compiles a cell, and 1,200 s spare, inside 43,200 s."""
    cells = 24
    total = ((2 + 14 * cells) * (spec["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_names_and_units(spec):
    groups = (spec["configs"], spec["workloads"], spec["end_to_end"],
              spec["per_layer"])
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])


@pytest.mark.parametrize("kind", ["configs", "traffic", "limits", "metrics"])
def test_names_resolve_to_files(spec, kind):
    if kind == "configs":
        files = [c["file"] for c in spec["configs"]]
        for c in spec["configs"]:
            conf = _load(c["file"])
            assert conf["name"] == c["name"]
            assert conf["reduced"] == c["reduced"]
            assert os.path.isfile(os.path.join(ROOT, conf["reference"]))
        assert len(files) == len(set(files))
    elif kind == "traffic":
        for w in spec["workloads"]:
            mix = _load("bench", "traffic", w["traffic"] + ".json")
            assert mix["loop"] in ("open", "closed")
    elif kind == "limits":
        for w in spec["workloads"]:
            limits = _load("bench", "limits", w["name"] + ".json")
            assert limits and all(v >= 0 for v in limits.values())
    else:
        for m in spec["end_to_end"] + spec["per_layer"]:
            path = os.path.join(ROOT, "bench", "metrics", m["name"] + ".py")
            mod_spec = importlib.util.spec_from_file_location(
                "m_" + re.sub(r"\W", "_", m["name"]), path)
            assert mod_spec is not None, path
            assert "def read(ctx)" in open(path).read()


def test_cells_report_what_the_contract_asks(spec):
    configs = {c["name"] for c in spec["configs"]}
    used = {w["config"] for w in spec["workloads"]}
    assert used == configs
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, math.floor(len(spec["workloads"]) / 2))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in spec["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        mine = [m for m in spec["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layers = [m for m in spec["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert layers
        for m in layers:
            assert m["moves"] in {x["name"] for x in mine}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= {w["name"]
                                               for w in spec["workloads"]}


@pytest.mark.parametrize("config_file", sorted(os.listdir(
    os.path.join(ROOT, "bench", "configs"))))
def test_every_config_names_a_reference(config_file):
    """Each configuration names its architecture's module, a file under
    bench/models/ that exports the whole interface."""
    import run
    from models import INTERFACE

    conf = _load("bench", "configs", config_file)
    path = os.path.normpath(conf["reference"])
    assert path.startswith(os.path.join("bench", "models") + os.sep), path
    assert os.path.isfile(os.path.join(ROOT, path))
    mod = run.reference(conf["reference"])
    assert [f for f in INTERFACE if not callable(getattr(mod, f, None))] == []


# keys of the OPT block's model group, and its program config, that only
# bench/models/vq_opt.py may name
OPT_ONLY = re.compile(r"""\[\s*["'](n_heads|d_ff|pos_pool|codebook_size"""
                      r"""|max_seq)["']\s*\]|vq_opt_125m""")


def test_harness_names_no_architecture():
    """Outside bench/models/ no file of the harness reads an OPT-only key or
    the program's OPT configuration, so another architecture's cells need no
    edit of it."""
    found = []
    bench = os.path.join(ROOT, "bench")
    for dirpath, dirnames, filenames in os.walk(bench):
        if dirpath == bench:
            dirnames.remove("models")
        for f in filenames:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    for i, line in enumerate(fh, 1):
                        if OPT_ONLY.search(line):
                            found.append(f"{os.path.relpath(path, ROOT)}:{i}")
    assert found == []
