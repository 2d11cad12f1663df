#!/usr/bin/env python3
"""Chip benchmark of the incremental edit server.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``<name>`` is a cell of ``BENCHMARK.json``: one configuration
(``bench/configs/<config>.json``: model sizes, server settings, and under
``reference`` its architecture's module in ``bench/models/``) under one
traffic mix (``bench/traffic/<traffic>.json``), with the limits of its
correctness check in ``bench/limits/<name>.json``. Every metric is a reader
of its own, ``bench/metrics/<metric>.py``. A run is one process: it fails
without a TPU, makes the weights from the seed, builds the server, opens the
documents, warms every shape the cell serves, serves the mix's warm-up, then
measures for ``--seconds``. With ``--trace 0`` the result line holds the
cell's end-to-end metrics; with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window and the server's counters. After the
window the program is freed and the served output is compared with the
architecture's reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``: every number compared, beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))  # the system under test

TRACE_CAP_S = 10.0  # a traced run traces at most this much of its window
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload: str, root: str = ROOT) -> SimpleNamespace:
    """A cell of ``BENCHMARK.json`` with its configuration, the module of its
    architecture, its mix, its limits and the metrics it reports."""
    spec = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(root, conf["file"])

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return SimpleNamespace(
        cell=cell, config=config, root=root,
        module=reference(config["reference"], root),
        mix=load_json(root, "bench", "traffic", cell["traffic"] + ".json"),
        limits=load_json(root, "bench", "limits", workload + ".json"),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def reference(path: str, root: str = ROOT):
    """The architecture module at ``path`` (relative to ``root``), imported
    by its path as ``models.<file name>``: one module object per file."""
    from models import INTERFACE

    path = os.path.realpath(os.path.join(root, path))
    name = "models." + os.path.splitext(os.path.basename(path))[0]
    mod = sys.modules.get(name)
    if mod is None or os.path.realpath(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    missing = [f for f in INTERFACE if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"{path} lacks {missing} of models.INTERFACE")
    return mod


def reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chip_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices


def use_cache(cache_dir: str) -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, unbounded, for every compile."""
    import jax

    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; a missing answer is infinite."""
    v = sorted(values)
    if not v:
        return math.inf
    return v[max(math.ceil(q / 100.0 * len(v)) - 1, 0)]


# ---------------------------------------------------------------- the run


def serve_window(asrv, plan, phase: str, tickets, sent, seconds: float):
    import drive

    if plan.mix["loop"] == "open":
        return drive.run_open(asrv, plan, phase, tickets, sent)
    return drive.run_closed(asrv, plan, phase, seconds, tickets, sent)


def collect(srv, plan, replay: dict, streams, tickets) -> list:
    """Read every document back from the server (see ``check.compare``)."""
    import numpy as np

    docs = []
    for s in plan.sessions:
        st = srv.state(s.doc_id)
        valid = np.asarray(st.valid)
        pos = np.asarray(st.positions)
        order = np.argsort(np.where(valid, pos, np.iinfo(np.int32).max),
                           kind="stable")[:int(valid.sum())]
        d = {"replay": replay[s.doc_id],
             "served": [int(t) for t in srv.tokens(s.doc_id)],
             "device": [int(t) for t in np.asarray(st.tokens)[order]],
             "positions": pos[order].astype(np.int32),
             "xs": np.asarray(st.x)[:, order],
             "codes": np.asarray(st.codes)[:, order], "suggestion": None}
        if streams is not None:
            ev = streams.events[s.doc_id]
            if ev:
                d["suggestion"] = ev[-1][1]
            d["stale"] = not ev or ev[-1][2] < len(tickets[s.doc_id])
        docs.append(d)
    return docs


def run_cell(c: SimpleNamespace, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, control: bool = False,
             cache_dir: str = CACHE_DIR, trace_dir: str = TRACE_DIR,
             t_start: float = T_START, dump_trace: str = None) -> dict:
    """One run of a cell (``resolve``). Returns the result object."""
    import jax

    devices = chip_devices(c.cell["chips"]) if require_chip else jax.devices()
    use_cache(cache_dir)

    import program
    import xplane as tr
    import work
    from check import compare, judge
    from clock import CompileClock
    from traffic import Plan

    clock = CompileClock()
    split = {}
    mark = time.perf_counter()

    def stage(name):
        nonlocal mark
        now = time.perf_counter()
        split[name] = now - mark
        mark = now

    m, serving = c.config["model"], c.config["serving"]
    params = c.module.make_params(m, seed)
    stage("weights_s")
    plan = Plan(c.mix, m["vocab"], seed, seconds)
    srv, asrv = program.build_server(params, c.module.arch_config(m), serving)
    stage("server_s")
    opens = [asrv.open_document(s.doc_id, s.base) for s in plan.sessions]
    for t in opens:
        t.result(600)
    tickets = {s.doc_id: [] for s in plan.sessions}
    sent = {s.doc_id: 0 for s in plan.sessions}
    streams = None
    if plan.subscribe:
        import drive

        streams = drive.Streams(asrv, plan, tickets)
    asrv.flush(600)
    stage("opens_s")
    program.warm_shapes(srv, [s.doc_id for s in plan.sessions],
                        plan.subscribe)
    stage("warm_shapes_s")
    compiles_warm = clock.compiles
    serve_window(asrv, plan, "warm", tickets, sent, float(c.mix["warmup_s"]))
    asrv.flush(600)
    stage("warmup_traffic_s")
    before = program.counters(srv, asrv)
    compiles_before = clock.snapshot()
    setup_s = time.perf_counter() - t_start

    spans, stopper = None, None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        spans = program.Spans(srv)
        trace_s = min(seconds, TRACE_CAP_S)

        def stop():
            with jax.profiler.TraceAnnotation("bench.mark.end"):
                spans.recording = False
            jax.profiler.stop_trace()

        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench.mark.start"):
            spans.recording = True
        stopper = threading.Timer(trace_s, stop)
        stopper.start()
    recs, t0, t_end = serve_window(asrv, plan, "window", tickets, sent,
                                   seconds)
    if stopper is not None:
        stopper.join()
    asrv.flush(120)
    after = program.counters(srv, asrv)
    compiles_after = clock.snapshot()
    failed_requests = asrv.stats.requests_failed
    shapes = sorted(map(str, getattr(srv, "_shapes_seen", ())))
    asrv.close(120)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices[:c.cell["chips"]]]

    replay = {s.doc_id: s.replay(sent[s.doc_id]) for s in plan.sessions}
    if streams is not None:
        for r in recs:
            hit = streams.first_after(r["doc"], r["k"] + 1)
            r["sugg"] = hit[0] if hit and r["ack"] != math.inf else math.inf
    served = collect(srv, plan, replay, streams, tickets)
    del srv, asrv, streams
    gc.collect()

    readings = compare(c.module, params, m, served,
                       max_len=c.mix["max_doc_len"])
    if failed_requests:
        readings["token_mismatch"] += failed_requests
    correct, rows = judge(readings, c.limits)
    out_extra = {}
    if control:
        ctl = compare(c.module, params, m, served,
                      max_len=c.mix["max_doc_len"], control=True)
        ctl_correct, ctl_rows = judge(ctl, c.limits)
        out_extra["control"] = {"correct": bool(ctl_correct),
                                "checks": ctl_rows, "readings": ctl}

    ctx = SimpleNamespace(
        recs=recs, t0=t0, t_end=t_end, window_s=seconds, setup_s=setup_s,
        before=before, after=after, model=m, trace=None, work=None,
        peaks=None, loop=c.mix["loop"])
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peak) if peak else 0}
    result = {"correct": bool(correct),
              "attempted": len(recs),
              "failed": sum(1 for r in recs if r["ack"] == math.inf),
              "metrics": {}, "device": device}
    if trace:
        if dump_trace:
            with open(dump_trace, "w") as f:
                json.dump(tr.describe(tr.find_xplane(trace_dir)), f)
        ctx.trace = tr.load(tr.find_xplane(trace_dir))
        ctx.work = work.dispatch_totals(c.module, m, spans.dispatches)
        ctx.peaks = work.peaks(devices[0].device_kind) if require_chip \
            else None
        device["busy_s"] = tr.busy_ns(ctx.trace) / 1e9
        device["window_s"] = ctx.trace.window_ns / 1e9
        result["breakdown"] = {"device_ops": tr.top_ops(ctx.trace),
                               "idle_gaps": tr.idle_gaps(ctx.trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    for spec in (c.per_layer if trace else c.end_to_end):
        value = reader(spec["name"], c.root)(ctx)
        if value is not None:
            result["metrics"][spec["name"]] = {"value": value,
                                               "unit": spec["unit"]}
    info = {"setup_split_s": split, "setup_s": setup_s,
            "compiles_warm_shapes": compiles_warm,
            "compiles_before_window": compiles_before,
            "compiles_in_window": compiles_after["compiles"]
            - compiles_before["compiles"],
            "window_counters": {k: after[k] - before.get(k, 0)
                                for k in after},
            "edits_scheduled": plan.window_edits(),
            "shapes_served": shapes, "readings": readings, **out_extra}
    result["info"] = info
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def finite(x):
    """JSON has no infinity: a missing answer prints as 1e300."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and math.isinf(x):
        return math.copysign(1e300, x)
    return x


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the control (the reference at the "
                         "precision below the configuration's)")
    ap.add_argument("--dump-trace", default=None,
                    help="write the traced run's planes, lines and busiest "
                         "event names to this JSON file")
    args = ap.parse_args(argv)
    try:
        cell = resolve(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=bool(args.control),
                          dump_trace=args.dump_trace)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    result = finite(result)
    info = result.pop("info")
    print(json.dumps({"run_info": info}), flush=True)
    if "control" in info:
        log(f"control correct: {info['control']['correct']}")
        for name, v, lim in info["control"]["checks"]:
            log(f"control check {name}: {v!r} (limit {lim!r})")
    log(f"correct: {result['correct']}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
