#!/usr/bin/env python3
"""Chip smoke: the batched edit server end to end on a TPU, at the full
width of vq-opt-125m (12 layers, d 768, 12 heads, d_ff 3072, vocab 50272,
a 204,800-id position pool), with random weights from ``--seed``.

    python chip_smoke.py             # one chip: the serving path, 12 layers
    python chip_smoke.py --chips 4   # the sharded path on a 4-chip mesh
                                     # against mesh 1, depth cut to 2 layers

The path is the one a client drives: ``AsyncBatchServer`` ->
``BatchServer`` -> ``BatchedJitEngine`` -> the compiled ``fused_step``
Pallas kernel. Eight documents of 512 tokens take a seeded mixed stream of
48 edits (about 40% inserts or deletes, so documents grow on the device out
of their full 512-slot capacity class) while each holds an 8-token
suggestion subscription. The script then checks what was served:

* the served tokens equal the traffic generator's own replay of the stream,
  and the device state's tokens equal the server's host mirror;
* every suggestion equals ``serving.suggest.oracle_suggestion``, a
  from-scratch decode, token for token;
* every document's served logits equal a from-scratch full forward of the
  final document within ``LOGITS_ATOL``.

The timings it prints are smoke numbers, not a benchmark. It exits
non-zero, printing no result line, when JAX finds no TPU or any check
fails; on success its last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiles are kept in JAX's persistent cache (``repro.common.compile_cache``),
so a second run reports fewer compile seconds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_DOCS = 8
DOC_LEN = 512
EDITS_PER_DOC = 6
N_NEW = 8
EDIT_CAPACITY = 8
ROW_CAPACITY = 64
MAX_BATCH = 8
# step-2 capacity classes put a 512-token document in a full 512-slot class,
# so its first insert grows it on the device (pad_state) to 1024 slots
CAPACITY_CLASS_STEP = 2
# served-vs-from-scratch logits: identical VQ codes leave only f32
# accumulation-order differences (~1e-5 at these widths); a code that
# differs moves a logit by O(0.1)
LOGITS_ATOL = 1e-3
FOUR_CHIP_LAYERS = 2
TIMEOUT_S = 1500.0


class CheckFailed(RuntimeError):
    pass


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), the number of backend compiles, and how many of
    those the persistent cache answered, from JAX's own monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]

    def _on_count(self, event: str, **_) -> None:
        self.cache_hits += event == self.CACHE_HIT

    def reset(self) -> None:
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

    def __str__(self) -> str:
        return (f"compile set-up {self.seconds:.1f} s over {self.compiles} "
                f"compiles ({self.cache_hits} from the persistent cache)")


def log(msg: str) -> None:
    print(msg, flush=True)


def make_traffic(vocab: int, seed: int):
    """Base documents, each document's op stream and the generator's own
    replay of it (the reference for the served tokens)."""
    from repro.data.edit_stream import TrafficGenerator

    # revise bursts only: 60% replace, 20% delete, 20% insert
    gen = TrafficGenerator(vocab=vocab, n_docs=N_DOCS, doc_len=DOC_LEN,
                           seed=seed, p_typing=0.0)
    docs, ops, refs = {}, {}, {}
    for i in range(N_DOCS):
        d = f"doc{i}"
        docs[d] = gen.base_document(i)
        refs[d] = list(docs[d])
        ops[d] = gen.session_ops(i, EDITS_PER_DOC, refs[d])
    return docs, ops, refs


def serve(srv, docs: dict, ops: dict) -> dict:
    """One pass of the stream through the async front end. Returns each
    document's final suggestion after checking every ticket and stream."""
    from repro.serving.async_server import AsyncBatchServer

    # The whole stream is admitted inside one deadline window, and the
    # bucket (one larger than the document count) never fills early, so
    # every pass forms the same rounds and hence the same compiled shapes.
    with AsyncBatchServer(srv, max_batch_delay_ms=200.0,
                          bucket_docs=len(docs) + 1) as asrv:
        tickets = [asrv.open_document(d, toks) for d, toks in docs.items()]
        streams = {d: asrv.subscribe(d, N_NEW) for d in docs}
        for k in range(max(len(v) for v in ops.values())):
            for d, stream in ops.items():
                if k < len(stream):
                    kind, pos, tok = stream[k]
                    if kind == "replace":
                        tickets.append(asrv.submit_replace(d, pos, tok))
                    elif kind == "insert":
                        tickets.append(asrv.submit_insert(d, pos, tok))
                    else:
                        tickets.append(asrv.submit_delete(d, pos))
        for t in tickets:
            t.result(TIMEOUT_S)
        asrv.flush(TIMEOUT_S)
        finals = {d: asrv.suggest(d, N_NEW).result(TIMEOUT_S) for d in docs}
        if asrv.stats.requests_failed:
            raise CheckFailed(f"{asrv.stats.requests_failed} requests failed")
    for d, s in streams.items():
        last = None
        while True:
            try:
                kind, _serial, *rest = s.get(timeout=0)
            except TimeoutError:
                break
            if kind == "suggestion":
                last = rest[0]
        if last is None or list(last) != list(finals[d]):
            raise CheckFailed(f"{d}: subscription stream did not deliver the "
                              "final suggestion")
    return finals


def check_served(srv, params, cfg, refs: dict, finals: dict) -> list[str]:
    """Compare what the server holds against the references; returns the
    failures (empty when every check passed)."""
    import numpy as np

    import jax.numpy as jnp

    from repro.serving.batch_server import _device_copy
    from repro.serving.suggest import oracle_suggestion

    eng = srv.engine(srv.C, srv.R)
    failures = []
    worst_logit = 0.0
    for d, ref in refs.items():
        doc = srv.docs[d]
        served = srv.tokens(d)
        st = srv.state(d)
        valid = np.asarray(st.valid)
        order = np.argsort(np.asarray(st.positions)[valid], kind="stable")
        on_device = np.asarray(st.tokens)[valid][order]
        want = oracle_suggestion(params, cfg, eng, doc.tokens, doc.positions,
                                 doc.valid, N_NEW, suggester=srv.suggester)
        # device copies of the live host mirrors
        scratch = eng.full_forward(_device_copy(doc.tokens),
                                   _device_copy(doc.positions),
                                   _device_copy(doc.valid))
        ref_logits = np.asarray(eng.logits_at(scratch,
                                              jnp.int32(doc.slots[-1])))
        dlogit = float(np.max(np.abs(srv.logits(d) - ref_logits)))
        worst_logit = max(worst_logit, dlogit)
        code_diff = int(np.sum(np.any(
            np.asarray(st.codes) != np.asarray(scratch.codes), axis=-1)
            & valid[None]))
        ok_tok = served.tolist() == list(ref)
        ok_dev = on_device.tolist() == served.tolist()
        ok_sugg = list(finals[d]) == list(want)
        ok_logit = dlogit <= LOGITS_ATOL
        log(f"  {d}: n={doc.n} n_cap={doc.n_cap} R={doc.row_capacity} "
            f"tokens={'ok' if ok_tok and ok_dev else 'MISMATCH'} "
            f"suggestion={'ok' if ok_sugg else 'MISMATCH'} "
            f"{[int(t) for t in finals[d]]} max|dlogit|={dlogit:.3e} "
            f"rows with a code unlike the from-scratch forward={code_diff}")
        if not ok_tok:
            failures.append(f"{d}: served tokens differ from the replay")
        if not ok_dev:
            failures.append(f"{d}: device tokens differ from the host mirror")
        if not ok_sugg:
            failures.append(f"{d}: suggestion {[int(t) for t in finals[d]]} "
                            f"!= oracle {[int(t) for t in want]}")
        if not ok_logit:
            failures.append(f"{d}: max|dlogit| {dlogit:.3e} > {LOGITS_ATOL}")
    log(f"logits bound: max|served - from-scratch| = {worst_logit:.3e} "
        f"(bound {LOGITS_ATOL})")
    return failures


def new_server(params, cfg, mesh=None):
    from repro.serving.batch_server import BatchServer

    return BatchServer(params, cfg, edit_capacity=EDIT_CAPACITY,
                       row_capacity=ROW_CAPACITY, max_batch=MAX_BATCH,
                       capacity_class_step=CAPACITY_CLASS_STEP, mesh=mesh)


def run_one_chip(cfg, params, traffic, clock: CompileClock) -> None:
    docs, ops, refs = traffic
    n_edits = sum(len(v) for v in ops.values())
    structural = sum(op[0] != "replace" for v in ops.values() for op in v)
    log(f"stream: {len(docs)} docs x {DOC_LEN} tokens, {n_edits} edits "
        f"({structural} inserts/deletes), {N_NEW}-token subscriptions")
    srv = new_server(params, cfg)
    clock.reset()
    t0 = time.perf_counter()
    finals = serve(srv, docs, ops)
    cold = time.perf_counter() - t0
    s = srv.stats
    log(f"cold pass: {cold:.1f} s wall, {clock}")
    log(f"server: dispatches={s.batch_steps} shapes={s.traced_shapes} "
        f"overflows={s.overflows} full_forwards={s.full_forwards} "
        f"device_grows={s.device_grows} defrags={s.defrags} "
        f"suggest_refreshes={s.suggest_refreshes}")
    failures = [] if s.device_grows else ["no document crossed a capacity "
                                          "class (device grow never ran)"]
    failures += check_served(srv, params, cfg, refs, finals)

    import jax
    import jax.numpy as jnp

    # the largest edit-step shape the pass dispatched: compiling it again
    # is a persistent-cache hit
    _, B, n_cap, C, R = max(x for x in srv._shapes_seen if x[0] == "edit")
    eng = srv.engine(C, R)
    slots = jax.ShapeDtypeStruct((n_cap,), jnp.int32)
    one = jax.eval_shape(eng._full_forward_impl, eng.wts, slots, slots,
                         jax.ShapeDtypeStruct((n_cap,), bool))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((B,) + a.shape, a.dtype), one)
    bucket = jax.ShapeDtypeStruct((B, C), jnp.int32)
    hlo = eng._batch_apply_edits_local.jitted.lower(
        eng, eng.wts, state, bucket, bucket, bucket, bucket).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"compiled edit step (B={B}, n_cap={n_cap}, C={C}, R={R}) contains "
        f"tpu_custom_call: {has_kernel}")
    if not has_kernel:
        failures.append("the compiled edit step runs no Pallas kernel")

    for d in docs:
        srv.close_document(d)
    clock.reset()
    t0 = time.perf_counter()
    serve(srv, docs, ops)
    warm = time.perf_counter() - t0
    log(f"warm pass (smoke number, not a benchmark): {warm:.2f} s wall, "
        f"{1e3 * warm / n_edits:.1f} ms per edit including opens and "
        f"suggestion refreshes; {clock.compiles} compiles in the window")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {peak}")
    if failures:
        raise CheckFailed("; ".join(failures))


def run_four_chips(cfg, params, traffic, clock: CompileClock) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh

    docs, ops, refs = traffic
    served = {}
    for name, n in (("mesh4", 4), ("mesh1", 1)):
        srv = new_server(params, cfg, mesh=make_serving_mesh(n))
        clock.reset()
        t0 = time.perf_counter()
        finals = serve(srv, docs, ops)
        log(f"{name}: {time.perf_counter() - t0:.1f} s wall, {clock}, "
            f"dispatches={srv.stats.batch_steps} "
            f"sharded={srv.stats.sharded_dispatches} "
            f"grows={srv.stats.device_grows}")
        served[name] = dict(
            srv=srv, suggestions=finals,
            tokens={d: srv.tokens(d).tolist() for d in docs},
            logits={d: np.asarray(srv.logits(d)) for d in docs})
    a, b = served["mesh4"], served["mesh1"]
    failures = []
    worst = 0.0
    for d, ref in refs.items():
        if a["tokens"][d] != list(ref) or b["tokens"][d] != list(ref):
            failures.append(f"{d}: served tokens differ from the replay")
        if list(a["suggestions"][d]) != list(b["suggestions"][d]):
            failures.append(f"{d}: mesh-4 suggestion differs from mesh 1")
        worst = max(worst, float(np.max(np.abs(a["logits"][d]
                                                - b["logits"][d]))))
    log(f"mesh 4 vs mesh 1: tokens and suggestions "
        f"{'equal' if not failures else 'DIFFER'}; max|dlogit| = "
        f"{worst:.3e} (bound {LOGITS_ATOL})")
    if worst > LOGITS_ATOL:
        failures.append(f"mesh-4 logits differ by {worst:.3e}")
    srv4 = a["srv"]
    if srv4.stats.sharded_dispatches == 0:
        failures.append("no dispatch ran over the 4-way mesh")
    # where a dispatch's batched state lives: the ingest of the base
    # documents (one compiled shape the server already ran)
    eng4 = srv4.engine(srv4.C, srv4.R)
    toks = np.stack([np.asarray(t, np.int32) for t in docs.values()])
    pos = np.tile(np.arange(DOC_LEN, dtype=np.int32)
                  * (srv4.pos_pool // DOC_LEN), (len(docs), 1))
    state = eng4.batch_full_forward(jax.numpy.asarray(toks),
                                    jax.numpy.asarray(pos),
                                    jax.numpy.ones(toks.shape, bool))
    rows = {str(sh.device): int(sh.data.shape[0])
            for sh in state.x.addressable_shards}
    log(f"batched state rows per device: {rows}")
    if len(rows) != 4 or set(rows.values()) != {len(docs) // 4}:
        failures.append(f"batched state not split over 4 devices: {rows}")
    resident = {str(dev) for doc in srv4.docs.values()
                for dev in doc.state.x.devices()}
    log(f"per-document states at rest live on: {sorted(resident)}")
    if failures:
        raise CheckFailed("; ".join(failures))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded serving path against "
                         "mesh 1, on a four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.common.compile_cache import enable_compilation_cache
    from repro.configs.vq_opt_125m import config
    from repro.models import transformer as T

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    cache_dir = enable_compilation_cache()
    clock = CompileClock()
    cfg = config()
    if args.chips == 4:
        cfg = dataclasses.replace(
            cfg, n_layers=FOUR_CHIP_LAYERS,
            stages=((cfg.stages[0][0], FOUR_CHIP_LAYERS),))
    log(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}); "
        f"compile cache: {cache_dir}")
    log(f"config: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
        f"heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"pos_pool={cfg.pos_pool} vq_heads={cfg.vqt.n_heads} "
        f"codebook={cfg.vqt.codebook_size}; edit_capacity={EDIT_CAPACITY} "
        f"row_capacity={ROW_CAPACITY} max_batch={MAX_BATCH} seed={args.seed}")
    t0 = time.perf_counter()
    params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
    traffic = make_traffic(cfg.vocab, args.seed)
    log(f"set-up: weights and traffic in {time.perf_counter() - t0:.1f} s")
    try:
        if args.chips == 4:
            run_four_chips(cfg, params, traffic, clock)
        else:
            run_one_chip(cfg, params, traffic, clock)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
