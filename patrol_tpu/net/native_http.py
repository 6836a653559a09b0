"""Native HTTP front: the C++ epoll server (native/patrol_http.cpp) pumped
by a Python batch loop.

The reference serves /take from compiled Go net/http (command.go:41-44);
the asyncio front (net/api.py) is the protocol-complete equivalent but
pays Python per request. This front moves the entire socket path — accept,
epoll, HTTP parse, percent-decoding, Go-semantics rate parsing, response
formatting — into C++, and crosses into Python in BATCHES:

* the pump thread drains up to ``batch`` parsed /take records in ONE
  ctypes call and submits them as engine tickets (they coalesce into the
  same device tick), each addressed to the front's :class:`AnswerSink`;
  whichever thread completes a batch of tickets hands their answers back
  in ONE call (``pt_http_answer_takes``, keeping the interpreter lock);
* non-/take routes (debug, metrics — rare) are dispatched to the existing
  :class:`patrol_tpu.net.api.API` handlers on a private asyncio loop, so
  both fronts share one routing/semantics implementation.

h2c (prior-knowledge) IS spoken natively (r5, VERDICT r4 item 9): the C++
front serves h2 framing directly for the API's bodyless shapes, with
HPACK decoding delegated to the system libnghttp2 inflater — native-class
rps for h2 clients. When libnghttp2 is absent, preface-bearing
connections splice byte-for-byte to the loopback python h2 server
(the r4 bridge); the h1→h2c Upgrade dance remains python-front-only.
"""

from __future__ import annotations

import asyncio
import collections
import ctypes
import logging
import threading
import time

import numpy as np

from patrol_tpu import native
from patrol_tpu.ops.rate import Rate
from patrol_tpu.utils import histogram as hist
from patrol_tpu.utils import profiling
from patrol_tpu.utils import trace as trace_mod

log = logging.getLogger("patrol.native-http")

NAME_MAX = 256
# A native take unanswered this long snapshots the flight recorder (the
# pump's take-stall check); it is still answered when it completes.
STALL_NS = 1_000_000_000


class AnswerSink:
    """The native front's take answers on their way back to the C++ front.

    A ticket submitted by the pump carries this sink, its connection tag
    and h2 stream. ``TakeTicket.complete`` queues it (:meth:`add`); the
    code that completed a batch of tickets then calls :meth:`flush` once
    (``engine.hand_back``), which hands every pending answer over in one
    ``pt_http_answer_takes`` call on that thread. The binding keeps the
    interpreter lock (``NATIVE_EFFECTS`` ``keeps_gil``), so no answer
    waits for a thread to take the lock back; the call appends under a
    leaf mutex and the epoll thread queues the responses. The pump also
    flushes on every poll, a backstop that should find nothing.

    Each pump batch is open from before its submit until its last answer
    is handed back: answers of a batch still being submitted wait in the
    queue (so a batch that fails mid-submit is answered 500, once), and
    the batch's last answer closes its ``http_front_wait_ns`` sample."""

    def __init__(self, lib, h: int, cap: int):
        self._lib = lib
        self._h = h  # -1 once detached: answers are dropped, not sent
        self._mu = threading.Lock()
        self._pending: collections.deque = collections.deque()
        self.add = self._pending.append  # TakeTicket.complete's hook
        # pump batch id -> [answers left, poll stamp, submit stamp (0
        # while the submit runs)], oldest first
        self._open: dict = {}
        self._answer = hist.FRONT_ANSWER  # complete → handed back
        self._alloc(cap)

    def _alloc(self, cap: int) -> None:
        self._tags = np.zeros(cap, np.uint64)
        self._streams = np.zeros(cap, np.int32)
        self._statuses = np.zeros(cap, np.int32)
        self._remaining = np.zeros(cap, np.int64)

    def open(self, batch: int, n: int, t_poll: int) -> None:
        with self._mu:
            self._open[batch] = [n, t_poll, 0]

    def submitted(self, batch: int, t_sub: int) -> None:
        with self._mu:
            self._open[batch][2] = t_sub

    def drop(self, batch: int) -> None:
        """A batch whose submit failed: its answers are never sent."""
        with self._mu:
            self._open.pop(batch, None)

    def oldest(self):
        """(batch id, poll stamp) of the oldest batch with an answer
        outstanding, or None."""
        with self._mu:
            for batch, rec in self._open.items():
                return batch, rec[1]
        return None

    def detach(self) -> None:
        """The front is closing: nothing is sent from here on."""
        with self._mu:
            self._h = -1
            self._open.clear()
            self._pending.clear()

    def flush(self, direct: bool = True) -> None:
        """Hand every pending answer back in one native call. ``direct``:
        called by the code that completed them (``http_answers_direct``),
        else by the pump's backstop (``http_answers_backstop``). A call
        that fails is logged, and its answers wait in the queue for the
        next flush."""
        pending = self._pending
        if not pending:
            return
        with self._mu:
            if self._h < 0:
                pending.clear()
                return
            opened = self._open
            live = []
            held = []
            for _ in range(len(pending)):
                t = pending.popleft()
                rec = opened.get(t.batch)
                if rec is None:
                    continue  # its batch failed: answered 500 already
                (live if rec[2] else held).append(t)
            pending.extend(held)
            n = len(live)
            if not n:
                return
            if n > len(self._tags):
                self._alloc(2 * n)
            tags, streams = self._tags, self._streams
            statuses, remaining = self._statuses, self._remaining
            oldest = live[0].batch
            for i, t in enumerate(live):
                tags[i] = t.tag
                streams[i] = t.stream
                statuses[i] = 200 if t.ok else 429
                remaining[i] = t.remaining
                if t.batch < oldest:
                    oldest = t.batch
            try:
                with trace_mod.TRACE.span(trace_mod.EV_HTTP_ANSWER, n, oldest):
                    self._lib.pt_http_answer_takes(
                        self._h, tags, streams, statuses, remaining, n
                    )
                    t_handed = time.perf_counter_ns()
            except Exception:
                # Never into the completing code (the engine's threads):
                # the answers go back to the queue for the pump's flush.
                log.exception("take answer hand-back failed; the pump retries")
                pending.extendleft(reversed(live))
                return
            waits = []
            for t in live:
                rec = opened[t.batch]
                rec[0] -= 1
                if not rec[0]:
                    # The batch's last answer: submit → handed back.
                    waits.append(t_handed - rec[2])
                    del opened[t.batch]
        if direct:
            profiling.COUNTERS.inc("http_answers_direct", n)
        else:
            profiling.COUNTERS.inc("http_answers_backstop", n)
        self._record_answers(live, t_handed)
        for w in waits:
            # patrol-scope: the front's engine-wait latency, one
            # observation per pump batch — the Python-side complement of
            # the C++ server's own ring (http_latency_* in stats()).
            hist.FRONT_WAIT.record(w)

    def _record_answers(self, tickets, t_handed: int) -> None:
        """Each take's last seam stage: complete → handed back."""
        self._answer.record_many([t_handed - t.done_ns for t in tickets])


class NativeHTTPFront:
    """C++ epoll HTTP/1.1 server + Python batch pump. h2c clients are
    spliced byte-for-byte to a loopback python h2 server when one is
    configured via :meth:`set_h2_backend` (protocol parity with the
    reference's h2c front, command.go:41-44, at the python front's
    throughput; h1 keep-alive stays on the C++ fast path)."""

    def __init__(self, api, host: str, port: int, batch: int = 1024):
        lib = native.load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self.lib = lib
        self.api = api
        self.h = lib.pt_http_start(host.encode(), port)
        if self.h < 0:
            import os

            raise OSError(-self.h, os.strerror(-self.h))
        self.h2_backend_port = 0
        # In-front host serving (VERDICT r4 item 1): when the engine owns
        # a native host-lane store, the epoll thread serves host-resident
        # takes entirely in C++; the pump then also drains the store's
        # coalesced broadcast/promotion events each cycle.
        self._engine = getattr(getattr(api, "repo", None), "engine", None)
        store = getattr(self._engine, "_native_store", None)
        if store is not None and self._engine.directory._ptdir >= 0:
            lib.pt_http_attach_host(
                self.h, store.h, self._engine.directory._ptdir
            )
        self.batch = batch
        b = batch
        self._tags = np.zeros(b, np.uint64)
        self._streams = np.zeros(b, np.int32)  # h2 stream ids (0 = h1)
        self._names = np.zeros((b, NAME_MAX), np.uint8)
        self._name_lens = np.zeros(b, np.int32)
        self._freqs = np.zeros(b, np.int64)
        self._pers = np.zeros(b, np.int64)
        self._counts = np.zeros(b, np.int64)
        self._req_ns = np.zeros(b, np.int64)  # parse stamps (CLOCK_MONOTONIC)
        self._batch = 0  # pump batch id, the seam's request id
        # The seam's per-take histograms (bound once, so a test's front
        # can be given its own): parse → poll, poll → ticket; the sink
        # binds complete → handed back.
        self._seam = (hist.FRONT_QUEUE, hist.FRONT_SUBMIT)
        self._sink = AnswerSink(lib, self.h, b)
        ob = 64
        self._otags = np.zeros(ob, np.uint64)
        self._ostreams = np.zeros(ob, np.int32)
        self._otargets = np.zeros((ob, native.PATH_MAX), np.uint8)
        self._otarget_lens = np.zeros(ob, np.int32)
        self._omethods = np.zeros((ob, 8), np.uint8)
        self._ob = ob

        self._stopped = threading.Event()
        # Private loop for the async debug handlers (they use
        # run_in_executor internally, so they need a real running loop).
        self._loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self._run_loop, name="patrol-http-debug", daemon=True
        )
        self._loop_thread.start()
        # The pump never waits on a tick: batch N+1 is drained and
        # submitted WHILE batch N's device tick runs, and the engine's
        # completer hands batch N's answers back.
        self._pump_thread = threading.Thread(
            target=self._pump, name="patrol-http-pump", daemon=True
        )
        self._pump_thread.start()

    @property
    def port(self) -> int:
        return self.lib.pt_http_port(self.h)

    def _run_loop(self) -> None:
        asyncio.set_event_loop(self._loop)
        self._loop.run_forever()

    # -- the batch pump ------------------------------------------------------

    def _pump(self) -> None:
        repo = self.api.repo
        n_other = ctypes.c_int(0)
        # With a host store attached, dirty (coalesced-broadcast) marks
        # deliberately do NOT wake the poll — a take must never pay a pump
        # wakeup on its latency path — so the poll tick is shortened to
        # bound broadcast delay instead (≤5 ms to peers; replication is
        # eventual by design). Promotions still wake the poll predicate.
        store = getattr(self._engine, "_native_store", None)
        poll_ms = 5 if store else 50
        next_drain = 0.0
        # Promotion-event cursor: the store's counter moves ONLY on
        # take-pressure promotion threshold crossings, so a poll woken
        # early by one can bypass the drain cadence below for a
        # promotions-only drain (ADVICE r5) — a newly-hot bucket must
        # not wait out max(poll tick, 4x last drain cost) to leave the
        # slow path. Broadcast building keeps the cadence gate.
        events_seen = store.events if store is not None else 0
        tr = trace_mod.TRACE
        sink = self._sink
        stalled = 0  # the batch the last take-stall snapshot was for
        while not self._stopped.is_set():
            nt = self.lib.pt_http_poll(
                self.h, poll_ms,
                self._tags, self._streams, self._names, self._name_lens,
                self._freqs, self._pers, self._counts, self._req_ns,
                self.batch,
                self._otags, self._ostreams, self._otargets,
                self._otarget_lens,
                self._omethods, self._ob, ctypes.byref(n_other),
            )
            if nt < 0:
                return
            if nt > 0:
                t_poll = time.perf_counter_ns()
                self._batch += 1
                with tr.span(trace_mod.EV_HTTP_PUMP, nt, self._batch):
                    self._submit_takes(repo, nt, t_poll, self._batch)
            sink.flush(direct=False)
            oldest = sink.oldest()
            if (
                oldest is not None
                and oldest[0] != stalled
                and time.perf_counter_ns() - oldest[1] > STALL_NS
            ):
                # A caller-visible take stall: freeze the flight recorder
                # so the timeline that led here is inspectable (once per
                # stalled batch; damped inside anomaly() besides).
                stalled = oldest[0]
                trace_mod.anomaly("take-stall")
            for j in range(n_other.value):
                self._dispatch_other(j)
            if self._engine is not None:
                drain = getattr(self._engine, "drain_native_broadcasts", None)
                now = time.monotonic()
                if drain is not None and now >= next_drain:
                    if store is not None:
                        events_seen = store.events
                    try:
                        drain()
                    except Exception:  # pragma: no cover
                        log.exception("native broadcast drain failed")
                    # Adaptive cadence: broadcast building must never own
                    # the core the epoll thread serves from — a drain that
                    # burned T of CPU doesn't rerun for 4T (≥ the poll
                    # tick). Coalescing makes the longer interval lossless
                    # (latest state subsumes); convergence lag stays
                    # bounded at ~4× the per-drain cost.
                    next_drain = time.monotonic()
                    next_drain += max(poll_ms / 1000.0, 4 * (next_drain - now))
                elif store is not None and store.events != events_seen:
                    # Cadence gate closed but a promote event woke the
                    # poll: promotions-only drain (dirty rows wait).
                    events_seen = store.events
                    try:
                        self._engine.drain_native_promotions()
                    except Exception:  # pragma: no cover
                        log.exception("native promotion drain failed")

    def _submit_takes(self, repo, nt: int, t_poll: int, batch: int) -> None:
        tags = self._tags[:nt].copy()
        streams = self._streams[:nt].copy()
        req_ns = self._req_ns[:nt].copy()
        names = [
            bytes(self._names[i, : self._name_lens[i]]).decode(
                "utf-8", "surrogateescape"
            )
            for i in range(nt)
        ]
        rates = [
            Rate(freq=int(self._freqs[i]), per_ns=int(self._pers[i]))
            for i in range(nt)
        ]
        counts = self._counts[:nt]
        reserved = [i for i in range(nt) if names[i].startswith("\x00")]
        if reserved:
            # NUL-led names are the replication control channel
            # (net/replication.py CTRL_PREFIX) — not a legal bucket
            # namespace. The python front 400s them in _decode_name;
            # mirror that here BEFORE the engine can bind a row (the
            # in-front C++ path only ever serves rows this pump created,
            # so rejecting creation closes the namespace on this front).
            sel = np.array(reserved, np.intp)
            self.lib.pt_http_answer_takes(
                self.h, tags[sel], streams[sel],
                np.full(len(sel), 400, np.int32),
                np.zeros(len(sel), np.int64), len(sel),
            )
            keep = [i for i in range(nt) if i not in set(reserved)]
            if not keep:
                return
            ksel = np.array(keep, np.intp)
            tags, streams, counts = tags[ksel], streams[ksel], counts[ksel]
            req_ns = req_ns[ksel]
            names = [names[i] for i in keep]
            rates = [rates[i] for i in keep]
        sink = self._sink
        n = len(names)
        sink.open(batch, n, t_poll)
        try:
            res = repo.submit_takes_batch(
                names, rates, counts, batch=batch,
                answer=(sink, tags, streams),
            )
            if res is None:  # pool spent with everything pinned: rare overload
                raise RuntimeError("bucket pool spent; takes dropped")
        except Exception:  # pragma: no cover - keep the front alive
            log.exception("take pump failed; answering 500")
            sink.drop(batch)
            self.lib.pt_http_answer_takes(
                self.h, tags, streams, np.full(n, 500, np.int32),
                np.zeros(n, np.int64), n,
            )
            return
        sink.submitted(batch, time.perf_counter_ns())
        # The answers this thread completed inside the submit (host-served
        # and shed takes), and any the engine completed meanwhile.
        sink.flush()
        self._record_intake(req_ns, t_poll, [t for t, _ in res])

    def _record_intake(self, req_ns, t_poll: int, tickets) -> None:
        """One pump batch's takes, each tiled parse → handed back across
        the seam: queue (parse → poll returned) and submit (poll returned
        → ticket created) here, the engine's own take_service_ns (created
        → complete), and the sink's answer stage (complete → handed
        back)."""
        queue, submit = self._seam
        queue.record_many([t_poll - r for r in req_ns.tolist()])
        submit.record_many([t.t0_ns - t_poll for t in tickets])

    def _dispatch_other(self, j: int) -> None:
        tag = int(self._otags[j])
        stream = int(self._ostreams[j])
        method = bytes(self._omethods[j]).split(b"\0", 1)[0].decode("ascii", "replace")
        target = bytes(self._otargets[j, : self._otarget_lens[j]]).decode(
            "utf-8", "surrogateescape"
        )
        path, _, query = target.partition("?")

        async def run():
            return await self.api.handle(method, path, query)

        fut = asyncio.run_coroutine_threadsafe(run(), self._loop)

        def done(f) -> None:
            try:
                status, body, ctype = f.result()
            except Exception:  # pragma: no cover
                log.exception("debug route failed")
                status, body, ctype = 500, b"internal error\n", "text/plain"
            self.lib.pt_http_complete_other(
                self.h, tag, stream, status, ctype.encode(), body, len(body)
            )

        fut.add_done_callback(done)

    # -- lifecycle / observability -------------------------------------------

    def set_h2_backend(self, port: int) -> None:
        """Enable h2c prior-knowledge: preface-bearing connections splice
        to the python h2 server at 127.0.0.1:``port``."""
        rc = self.lib.pt_http_set_h2_backend(self.h, port)
        if rc != 0:
            raise OSError(-rc, "pt_http_set_h2_backend failed")
        self.h2_backend_port = port

    def stats(self) -> dict:
        out = np.zeros(11, np.uint64)
        self.lib.pt_http_stats(self.h, out)
        return {
            "http_accepted": int(out[0]),
            "http_requests": int(out[1]),
            "http_active_conns": int(out[2]),
            "http_dropped": int(out[3]),
            # Server-side (parse → response queued), 4096-sample ring.
            "http_latency_p50_us": int(out[4]) // 1000,
            "http_latency_p99_us": int(out[5]) // 1000,
            "http_latency_max_us": int(out[6]) // 1000,
            # Take answers handed over (pt_http_answer_takes): calls that
            # waited for the answer queue's lock, answers the epoll thread
            # queued on their connections, and their summed handed →
            # queued time (cumulative).
            "http_answer_lock_waits": int(out[8]),
            "http_answers_drained": int(out[9]),
            "http_answer_drain_ns": int(out[10]),
        }

    def close(self) -> None:
        if self._stopped.is_set():
            return  # closed already: the handle may name another server now
        # Detach the host store FIRST (under the server mutex): the engine
        # destroys the store after this front closes, and the epoll thread
        # must never touch freed blocks — even on the leaked-server path.
        if self._engine is not None and getattr(self._engine, "_native_store", None):
            self.lib.pt_http_attach_host(self.h, -1, -1)
        self._stopped.set()
        self._pump_thread.join(timeout=5)
        # No answer is handed back from here on: a ticket completed later
        # is dropped from the sink, never sent to a stopping server.
        self._sink.detach()
        if self._pump_thread.is_alive():
            # pt_http_poll deliberately skips the registry lock (it
            # assumes the pump is joined first); destroying the Server
            # under a live pump would be a use-after-free. Leak the
            # native server instead — the process is shutting down anyway.
            # The host store must leak WITH it: a wedged pump may be
            # mid-drain inside the store, and engine.stop would otherwise
            # free the blocks under it.
            if self._engine is not None:
                self._engine._leak_native_store = True
            log.error(
                "http pump thread did not exit in 5s; leaking native server "
                "handle %d to avoid a use-after-free", self.h,
            )
        else:
            self.lib.pt_http_stop(self.h)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._loop_thread.join(timeout=5)


def available() -> bool:
    return native.load() is not None
