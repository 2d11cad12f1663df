"""Replica worker: a subprocess owning one ``AsyncBatchServer``.

Spawned by ``FleetRouter`` as ``python -m repro.serving.fleet.worker``. The
RPC transport is the stdin/stdout pipe pair in the length-prefixed framing
of ``fleet.protocol``; the FIRST frame on stdin is the replica spec (arch,
seed, cold directory, server knobs), after which the worker answers request
frames until stdin closes or a ``shutdown`` op arrives.

Determinism contract: every replica builds its parameters as
``init_params(PRNGKey(seed), cfg)`` on the same machine and jax build, so
all replicas (and the router-side oracle) hold bitwise-identical weights —
that, plus the serving-snapshot migration format, is what makes a migrated
document indistinguishable from one that never moved (DESIGN.md §11).

Two op families:

* **ticket ops** (``open`` / ``edit`` / ``suggest`` / ``tokens``) admit into
  the async front end and resolve when its scheduler serves them — many per
  frame pipeline into one deadline-batched round;
* **control ops** (``close`` / ``export`` / ``import`` / ``checkpoint`` /
  ``logits`` / ``evict`` / ``barrier`` / ``stats`` / ``shutdown``) first
  drain everything admitted before them (``AsyncBatchServer.flush``), then
  touch the inner ``BatchServer`` directly — safe because this process is
  the server's only client, and the drain preserves per-document order.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import traceback

from repro.serving.fleet import cold_tier
from repro.serving.fleet.protocol import recv_msg, send_msg

# tickets admitted by this worker resolve after at most one drain of its own
# scheduler; an hour means the scheduler thread is gone, not slow
_TICKET_TIMEOUT_S = 3600.0


class _Worker:
    def __init__(self, spec: dict):
        # jax lands here, not at module import: the router's per-replica
        # chip pinning (``router.worker_env``) is in the environment already
        import jax

        from repro.common.compile_cache import enable_compilation_cache
        from repro.configs import get_config
        from repro.models import transformer
        from repro.serving.async_server import AsyncBatchServer
        from repro.serving.batch_server import BatchServer

        # every replica replays the same compiled steps from one cache
        enable_compilation_cache()
        self.replica = spec["replica"]
        self.cold_dir = spec["cold_dir"]
        cfg = get_config(spec.get("arch", "vq-opt-125m"),
                         smoke=spec.get("smoke", True))
        params = transformer.init_params(
            jax.random.PRNGKey(spec.get("seed", 0)), cfg)
        self.srv = BatchServer(params, cfg, spill_dir=self.cold_dir,
                               **spec.get("server_kwargs", {}))
        self.asrv = AsyncBatchServer(self.srv, **spec.get("async_kwargs", {}))

    # ---------------------------------------------------------------- ops

    def _cold_path(self, doc_id: str) -> str:
        return cold_tier.cold_path_for(self.cold_dir, doc_id)

    def handle_frame(self, ops: list) -> tuple[list, bool]:
        """Serve one request frame. Returns (results, keep_running)."""
        results: list = [None] * len(ops)
        tickets: list = []  # (index, ticket) — resolved before returning
        shutdown = False

        def drain() -> None:
            """Order barrier before a control op: everything admitted so far
            (this frame's tickets included) is served."""
            self.asrv.flush()
            for i, t in tickets:
                results[i] = self._collect(t)
            tickets.clear()

        for i, op in enumerate(ops):
            kind = op["op"]
            try:
                if kind == "open":
                    cold_tier.acquire_lease(self.cold_dir, op["doc_id"],
                                            self.replica)
                    tickets.append(
                        (i, self.asrv.open_document(op["doc_id"],
                                                    op["tokens"])))
                elif kind == "edit":
                    doc, e = op["doc_id"], op["edit"]
                    if e[0] == "replace":
                        t = self.asrv.submit_replace(doc, e[1], e[2])
                    elif e[0] == "insert":
                        t = self.asrv.submit_insert(doc, e[1], e[2])
                    elif e[0] == "delete":
                        t = self.asrv.submit_delete(doc, e[1])
                    else:
                        raise ValueError(f"unknown edit kind {e[0]!r}")
                    tickets.append((i, t))
                elif kind == "suggest":
                    tickets.append(
                        (i, self.asrv.suggest(op["doc_id"], op["n_new"])))
                elif kind == "tokens":
                    tickets.append((i, self.asrv.tokens(op["doc_id"])))
                elif kind == "ping":
                    results[i] = {"ok": True, "value": {
                        "pid": os.getpid(), "replica": self.replica}}
                elif kind == "barrier":
                    drain()
                    results[i] = {"ok": True, "value": None}
                elif kind == "close":
                    drain()
                    self.asrv.close_document(op["doc_id"]).result(
                        _TICKET_TIMEOUT_S)
                    # a session close retires the document everywhere: any
                    # residual shared-tier snapshot and the lease go with it
                    path = self._cold_path(op["doc_id"])
                    if os.path.exists(path):
                        os.remove(path)
                    cold_tier.release_lease(self.cold_dir, op["doc_id"],
                                            self.replica)
                    results[i] = {"ok": True, "value": None}
                elif kind == "export":
                    drain()
                    path = self._cold_path(op["doc_id"])
                    self.srv.export_document(op["doc_id"], path)
                    cold_tier.release_lease(self.cold_dir, op["doc_id"],
                                            self.replica)
                    results[i] = {"ok": True, "value": path}
                elif kind == "import":
                    drain()
                    cold_tier.acquire_lease(self.cold_dir, op["doc_id"],
                                            self.replica)
                    try:
                        self.srv.import_document(
                            op["doc_id"], self._cold_path(op["doc_id"]),
                            remove=op.get("remove", True))
                    except Exception:
                        cold_tier.release_lease(self.cold_dir, op["doc_id"],
                                                self.replica)
                        raise
                    results[i] = {"ok": True, "value": None}
                elif kind == "checkpoint":
                    drain()
                    doc_ids = op.get("doc_ids") or list(self.srv.docs)
                    for d in doc_ids:
                        self.srv.checkpoint_document(d, self._cold_path(d))
                    results[i] = {"ok": True, "value": list(doc_ids)}
                elif kind == "logits":
                    drain()
                    import numpy as np  # device array -> picklable host copy
                    results[i] = {"ok": True,
                                  "value": np.asarray(
                                      self.srv.logits(op["doc_id"]))}
                elif kind == "evict":
                    drain()
                    results[i] = {"ok": True, "value": self.srv.evict(
                        op["doc_id"], op.get("tier", "warm"))}
                elif kind == "stats":
                    drain()
                    results[i] = {"ok": True, "value": self._stats()}
                elif kind == "reset_latency":
                    # benchmark timing protocol: warmup pays the compiles,
                    # then the histograms restart for the measured pass
                    drain()
                    from repro.serving.latency import LatencyStats
                    self.srv.stats.edit_latency = LatencyStats()
                    self.srv.stats.suggest_latency = LatencyStats()
                    results[i] = {"ok": True, "value": None}
                elif kind == "shutdown":
                    drain()
                    self.asrv.close()
                    shutdown = True
                    results[i] = {"ok": True, "value": None}
                else:
                    raise ValueError(f"unknown op {kind!r}")
            except Exception as exc:
                results[i] = _err(exc)
            if shutdown:
                break
        for i, t in tickets:
            results[i] = self._collect(t)
        # ops after a shutdown in the same frame are refused, not dropped
        for i in range(len(ops)):
            if results[i] is None:
                results[i] = _err(RuntimeError("worker is shutting down"))
        return results, not shutdown

    def _collect(self, ticket) -> dict:
        try:
            return {"ok": True, "value": ticket.result(_TICKET_TIMEOUT_S)}
        except Exception as exc:
            return _err(exc)

    def _stats(self) -> dict:
        out = {
            "replica": self.replica,
            "batch": dataclasses.asdict(self.srv.stats),
            "async": dataclasses.asdict(self.asrv.stats),
            "docs_open": len(self.srv.docs),
            "hot_hit_rate": self.srv.stats.hot_hit_rate,
        }
        if self.srv._sugg is not None:
            out["suggest"] = dataclasses.asdict(self.srv.suggest_stats)
        return out


def _err(exc: BaseException) -> dict:
    return {"ok": False, "error": f"{type(exc).__name__}: {exc}",
            "cls": type(exc).__name__}


def main() -> int:
    # Claim the RPC pipe BEFORE anything can print: frames go out on a dup
    # of the original stdout, while fd 1 is redirected to stderr so stray
    # writes (jax warnings, user prints) cannot corrupt the framing.
    rpc_out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    rpc_in = os.fdopen(os.dup(0), "rb")

    try:
        spec = recv_msg(rpc_in)
        worker = _Worker(spec)
    except Exception as exc:
        traceback.print_exc(file=sys.stderr)
        try:
            send_msg(rpc_out, {"ok": False, "error": str(exc)})
        except Exception:
            pass
        return 1
    send_msg(rpc_out, {"ok": True, "pid": os.getpid(),
                       "replica": worker.replica})
    running = True
    while running:
        try:
            req = recv_msg(rpc_in)
        except EOFError:
            # router gone (or clean stdin close): drain and exit quietly so
            # a crashed router never leaves orphan replicas behind
            try:
                worker.asrv.close()
            except Exception:
                pass
            break
        results, running = worker.handle_frame(req.get("ops", []))
        send_msg(rpc_out, {"id": req.get("id"), "results": results})
    return 0


if __name__ == "__main__":
    sys.exit(main())
