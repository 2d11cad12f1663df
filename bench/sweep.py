#!/usr/bin/env python3
"""Find the knee of an open-loop cell: serve its mix at each offered rate
in one process, on fresh documents each time, and report the latency and
the backlog (edits admitted and not yet acknowledged) over each window.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 10 20 40 ...

The knee is the highest rate at which the backlog does not grow over the
window; the cell's mix then offers a fixed rate below it. One JSON line per
rate on standard output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    c = run.resolve(args.workload)
    run.chip_devices(c.cell["chips"])
    run.use_cache(run.CACHE_DIR)

    import drive
    import program
    from traffic import Plan

    m = c.config["model"]
    params = c.module.make_params(m, args.seed)
    srv, asrv = program.build_server(params, c.module.arch_config(m),
                                     c.config["serving"])
    for idx, rate in enumerate(args.rates):
        mix = dict(c.mix, rate_edits_per_s=rate, warmup_s=0.0)
        plan = Plan(mix, m["vocab"], args.seed + idx, args.seconds)
        for t in [asrv.open_document(s.doc_id, s.base)
                  for s in plan.sessions]:
            t.result(600)
        tickets = {s.doc_id: [] for s in plan.sessions}
        sent = {s.doc_id: 0 for s in plan.sessions}
        streams = (drive.Streams(asrv, plan, tickets) if plan.subscribe
                   else None)
        asrv.flush(600)
        if idx == 0:
            program.warm_shapes(srv, [s.doc_id for s in plan.sessions],
                                plan.subscribe)
        samples, stop = [], threading.Event()

        def sample():
            while not stop.wait(0.25):
                n_sent = sum(len(v) for v in tickets.values())
                n_done = sum(t.done() for v in list(tickets.values())
                             for t in list(v))
                samples.append(n_sent - n_done)

        th = threading.Thread(target=sample, daemon=True)
        th.start()
        recs, t0, t_end = drive.run_open(asrv, plan, "window", tickets, sent)
        stop.set()
        th.join()
        asrv.flush(600)
        lat = [r["ack"] - r["due"] for r in recs]
        out = {"rate": rate, "edits": len(recs),
               "acked_in_window_per_s": sum(r["ack"] <= t_end for r in recs)
               / args.seconds,
               "ack_p50_ms": 1e3 * run.percentile(lat, 50),
               "ack_p95_ms": 1e3 * run.percentile(lat, 95),
               "late_p95_ms": 1e3 * run.percentile(
                   [r["sent"] - r["due"] for r in recs], 95),
               "backlog": samples}
        if streams is not None:
            sug = []
            for r in recs:
                hit = streams.first_after(r["doc"], r["k"] + 1)
                sug.append(hit[0] - r["due"] if hit else math.inf)
            out["suggest_p95_ms"] = 1e3 * run.percentile(sug, 95)
        print(json.dumps(out), flush=True)
        for t in [asrv.close_document(s.doc_id) for s in plan.sessions]:
            t.result(600)
    asrv.close(120)
    return 0


if __name__ == "__main__":
    sys.exit(main())
