"""Load drivers: the open loop (edits sent when due, whatever the server
does) and the closed loop (each session waits for its burst's acks).

Both drive the program's ``AsyncBatchServer`` through its client API only
and time every request on the host clock themselves. A failed request
counts as missing: its latency is infinite.
"""
from __future__ import annotations

import queue
import threading
import time

INF = float("inf")
ACK_GRACE_S = 60.0  # how long after the window an answer may still come


def submit(asrv, doc_id: str, op: tuple):
    _, _, kind, pos, tok = op
    if kind == "replace":
        return asrv.submit_replace(doc_id, pos, tok)
    if kind == "insert":
        return asrv.submit_insert(doc_id, pos, tok)
    return asrv.submit_delete(doc_id, pos)


class Streams:
    """Each session's subscription stream, with every delivered suggestion
    recorded as it is pushed: (time, tokens, edits of the document acked by
    then). Deliveries happen on the server's scheduler thread after the
    round's edit tickets resolve and before the next round starts, so the
    count of resolved tickets at that moment is exactly the set of edits the
    suggestion reflects."""

    def __init__(self, asrv, plan, tickets: dict):
        self.events = {s.doc_id: [] for s in plan.sessions}
        self._tickets = tickets
        self._acked = {s.doc_id: 0 for s in plan.sessions}
        self.streams = {}
        for s in plan.sessions:
            stream = asrv.subscribe(s.doc_id, plan.subscribe)
            push = stream._push

            def record(event, _d=s.doc_id, _push=push):
                if event[0] == "suggestion":
                    tl = self._tickets[_d]
                    k = self._acked[_d]
                    while k < len(tl) and tl[k].done():
                        k += 1
                    self._acked[_d] = k
                    self.events[_d].append(
                        (time.perf_counter(), list(event[2]), k))
                _push(event)

            stream._push = record
            self.streams[s.doc_id] = stream

    def first_after(self, doc_id: str, n_acked: int):
        """(time, tokens) of the first suggestion that reflects the
        document's first ``n_acked`` edits, or None."""
        for t, toks, k in self.events[doc_id]:
            if k >= n_acked:
                return t, toks
        return None


class Collector(threading.Thread):
    """Waits for tickets in submission order and stamps each acknowledgement.
    Tickets resolve a round at a time, in admission order, so the stamp
    lags the resolution by no more than the thread's wake-up."""

    def __init__(self):
        super().__init__(name="bench-collector", daemon=True)
        self.q: queue.Queue = queue.Queue()
        self.deadline = None

    def run(self) -> None:
        while True:
            item = self.q.get()
            if item is None:
                return
            ticket, rec = item
            left = (None if self.deadline is None
                    else max(self.deadline - time.perf_counter(), 0.0))
            try:
                ticket.result(left)
                rec["ack"] = time.perf_counter()
            except TimeoutError:
                rec["ack"] = INF
                rec["lost"] = True
            except Exception as e:  # the server failed the request
                rec["ack"] = INF
                rec["error"] = repr(e)


def run_open(asrv, plan, phase: str, tickets: dict, sent: dict) -> list:
    """Send every op of ``phase`` at its due time. Returns one record per
    op: due, submit and ack times (host clock), session, the op's index in
    the session's ticket list. Waits for every ack (at most
    ``ACK_GRACE_S`` past the schedule's end)."""
    sched = plan.schedule(phase)
    col = Collector()
    col.start()
    recs = []
    t0 = time.perf_counter()
    for due, i, j in sched:
        s = plan.sessions[i]
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        t_sub = time.perf_counter()
        ticket = submit(asrv, s.doc_id, s.ops[j])
        rec = {"due": t0 + due, "sent": t_sub, "doc": s.doc_id,
               "k": len(tickets[s.doc_id])}
        tickets[s.doc_id].append(ticket)
        sent[s.doc_id] += 1
        col.q.put((ticket, rec))
        recs.append(rec)
    t_end = t0 + (plan.seconds if phase == "window"
                  else float(plan.mix["warmup_s"]))
    col.deadline = max(t_end, time.perf_counter()) + ACK_GRACE_S
    col.q.put(None)
    col.join()
    return recs, t0, t_end


def run_closed(asrv, plan, phase: str, seconds: float, tickets: dict,
               sent: dict) -> tuple:
    """Each session sends its next burst as soon as the last one is fully
    acknowledged, until ``seconds`` have passed. Returns (records, t0,
    t_end); records as ``run_open``'s, with ``due`` the send time."""
    t0 = time.perf_counter()
    t_end = t0 + seconds
    recs, lock, errors = [], threading.Lock(), []

    def session(i: int) -> None:
        s = plan.sessions[i]
        try:
            while time.perf_counter() < t_end:
                lo, hi = plan.next_burst(i, phase)
                mine = []
                for j in range(lo, hi):
                    t_sub = time.perf_counter()
                    ticket = submit(asrv, s.doc_id, s.ops[j])
                    mine.append((ticket, {"due": t_sub, "sent": t_sub,
                                          "doc": s.doc_id,
                                          "k": len(tickets[s.doc_id])}))
                    tickets[s.doc_id].append(ticket)
                    sent[s.doc_id] += 1
                for ticket, rec in mine:
                    try:
                        ticket.result(max(t_end + ACK_GRACE_S
                                          - time.perf_counter(), 0.0))
                        rec["ack"] = time.perf_counter()
                    except TimeoutError:
                        rec["ack"], rec["lost"] = INF, True
                    except Exception as e:
                        rec["ack"], rec["error"] = INF, repr(e)
                with lock:
                    recs.extend(r for _, r in mine)
                if any(r["ack"] == INF for _, r in mine):
                    return
        except Exception as e:  # reported, never swallowed
            errors.append(repr(e))

    threads = [threading.Thread(target=session, args=(i,), daemon=True,
                                name=f"bench-session-{i}")
               for i in range(len(plan.sessions))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + ACK_GRACE_S + 30.0)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a closed-loop session did not finish")
    if errors:
        raise RuntimeError("; ".join(errors))
    return recs, t0, t_end
