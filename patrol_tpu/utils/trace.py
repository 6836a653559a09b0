"""patrol-scope flight recorder: per-thread ring buffers of ns-stamped
typed events, plus the cross-node take-span collector.

The reference's whole debug story is the pprof route set (api.go:29-39):
aggregate profiles, no *timeline*. The ingest wall (ROADMAP item 1) is
exactly the question aggregates cannot answer — where a delta spends its
time between the wire and the donated dispatch — so this module records
the pipeline's typed events (HTTP pump batch and answer, engine tick,
H2D put, kernel dispatch, completion, rx decode, fold, delta flush,
broadcast tx, GC sweep, audit and anti-entropy phases) into fixed-size
per-thread rings:

* **Lock-free on the hot path.** Each ring has exactly one writer (its
  owning thread); recording is a handful of list stores. With the
  recorder off (``PATROL_TRACE=0``) ``record`` returns at its first
  branch and :meth:`FlightRecorder.span` hands back one shared no-op
  scope — pinned by ``bench.py --smoke``'s ``trace_off_branch_ns``
  micro-test and ``tests/test_trace.py``.
* **One span call, two sinks.** :meth:`FlightRecorder.span` scopes
  same-thread work into the ring (start, duration, arg, a request or
  batch ``id`` and the ``cause`` that started it). While a profiler
  capture runs (``utils/profiling.py`` ``jax_trace`` raises
  :attr:`FlightRecorder.capturing`) the same scope also opens a
  ``jax.profiler.TraceAnnotation("patrol.<event>")``, so the span lands
  in the XPlane on the device trace's own clock. Ring stamps are
  CLOCK_MONOTONIC (``perf_counter_ns``, the C++ front's
  ``steady_clock``); each capture carries a ``patrol.clock`` annotation
  whose ``mono_ns`` metadata maps any ring stamp onto its timeline.
* **Bounded by construction.** ``PATROL_TRACE_RING`` events per thread
  (default 4096), oldest overwritten; a wedged consumer can never make
  the recorder grow.
* **Dumpable on demand** as Chrome-trace/Perfetto JSON via
  ``/debug/trace/ring`` (open in ``chrome://tracing`` or ui.perfetto.dev)
  and **auto-snapshotted on anomalies** — take stalls
  (``TakeTicket.wait`` timeout) and anti-entropy convergence-budget
  breaches call :func:`anomaly`, which freezes the rings into a bounded
  snapshot list served by ``/debug/trace/ring?snapshot=N``. Snapshots are
  damped to one per reason per second so a stall storm cannot turn the
  recorder into the bottleneck it is observing.

Cross-node take tracing (the span collector): a sampled take (1 in
``PATROL_TRACE_SAMPLE``; 0 disables) gets a process-unique trace id that
rides the replication datagram in a reserved trace trailer
(ops/wire.py) — invisible to v1 peers and to pre-trace patrol builds,
both of which ignore bytes past the trailers they know. The receiving
node stamps its decode and merge spans with the propagated id, so
``/debug/trace/spans?trace_id=N`` shows one take's full cross-node
story: local take span (node A) joined to the rx-decode and device-merge
spans (node B). Spans carry node slot + bucket name. The id rides the
python wire codec; the C++ batch encoder does not emit trace trailers
(native-backend broadcasts drop the id — tracing degrades, never
breaks).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from patrol_tpu.utils import profiling

# Event types (values are stable: they appear in dumps and snapshots).
EV_TICK = 1  # one feeder tick, drain through dispatch (arg = work rows, cause = oldest pump batch)
EV_H2D_PUT = 4  # host->device staging transfer shipped
EV_COMMIT_DISPATCH = 5  # donated kernel dispatch under _state_mu
EV_COMMIT_COMPLETE = 6  # one completion-pipeline callback (arg = tickets)
EV_RX_DECODE = 7  # replication rx decode (arg = packets)
EV_FOLD = 8  # host-side tick fold (arg = deltas folded)
EV_BROADCAST_TX = 9  # state fan-out, encode + sendmmsg (arg = datagrams)
EV_AE_PHASE = 10  # anti-entropy job (arg = phase code, see AE_PHASES)
EV_TAKE = 11  # one served take (sampled)
EV_ANOMALY = 12  # anomaly marker (snapshot trigger)
EV_DELTA_FLUSH = 13  # delta-plane pacing flush, pack + send (arg = datagrams)
EV_DELTA_ACK = 14  # delta ack vector sent/processed (arg = acks)
EV_DELTA_RETRANSMIT = 15  # expired intervals re-shipped (arg = intervals)
EV_DEVICE_READY = 16  # device dispatch→ready observed (arg = work rows)
EV_AUDIT_TICK = 17  # patrol-audit flush tick (arg = datagrams shipped)
EV_AUDIT_COMPARE = 18  # read-only divergence compare (arg = divergent buckets)
EV_TAKE_COALESCE = 19  # hot-key take-n rows in a tick (arg = tickets folded)
EV_HTTP_PUMP = 20  # native front: poll return to submit return (arg = takes, id = batch)
EV_HTTP_ANSWER = 21  # native front: a batch's answers handed back (arg = takes, id = batch)
EV_GC_SWEEP = 22  # bucket-lifecycle sweep (arg = buckets reclaimed)

EVENT_NAMES = {
    EV_TICK: "engine.tick",
    EV_H2D_PUT: "h2d.put",
    EV_COMMIT_DISPATCH: "commit.dispatch",
    EV_COMMIT_COMPLETE: "engine.complete",
    EV_RX_DECODE: "rx.decode",
    EV_FOLD: "fold",
    EV_BROADCAST_TX: "broadcast.tx",
    EV_AE_PHASE: "ae.phase",
    EV_TAKE: "take",
    EV_ANOMALY: "anomaly",
    EV_DELTA_FLUSH: "delta.flush",
    EV_DELTA_ACK: "delta.ack",
    EV_DELTA_RETRANSMIT: "delta.retransmit",
    EV_DEVICE_READY: "device.ready",
    EV_AUDIT_TICK: "audit.tick",
    EV_AUDIT_COMPARE: "audit.compare",
    EV_TAKE_COALESCE: "take.coalesce",
    EV_HTTP_PUMP: "http.pump",
    EV_HTTP_ANSWER: "http.answer",
    EV_GC_SWEEP: "gc.sweep",
}
# The XPlane name of each event's span: ``patrol.<event name>``.
ANNOTATION_PREFIX = "patrol."
_annotation_names = {k: ANNOTATION_PREFIX + v for k, v in EVENT_NAMES.items()}
_types_mu = threading.Lock()


def event_type(name: str) -> int:
    """The event type named ``name``, registered on first use. Kernel
    dispatch spans are typed this way (``runtime/engine.py``), so each
    kernel's span reads ``patrol.<kernel>`` in a capture."""
    with _types_mu:
        for k, v in EVENT_NAMES.items():
            if v == name:
                return k
        k = max(EVENT_NAMES) + 1
        EVENT_NAMES[k] = name
        _annotation_names[k] = ANNOTATION_PREFIX + name
        return k


AE_PHASES = {"trigger": 1, "digest": 2, "fetch": 3}

RING_SIZE = max(64, int(os.environ.get("PATROL_TRACE_RING", 4096)))


class _Ring:
    """One thread's fixed-size event ring. Parallel plain lists, single
    writer (the owning thread); readers copy — a torn read corrupts at
    most the event being written, never the reader."""

    __slots__ = (
        "tid", "name", "size", "etype", "t_ns", "dur_ns", "arg", "ids",
        "cause", "pos", "count",
    )

    def __init__(self, tid: int, name: str, size: int):
        self.tid = tid
        self.name = name
        self.size = size
        self.etype = [0] * size
        self.t_ns = [0] * size
        self.dur_ns = [0] * size
        self.arg = [0] * size
        self.ids = [0] * size
        self.cause = [0] * size
        self.pos = 0
        self.count = 0

    def events(self) -> List[tuple]:
        """Oldest-first copy of the live events (reader-side)."""
        et = list(self.etype)
        ts = list(self.t_ns)
        du = list(self.dur_ns)
        ar = list(self.arg)
        ids = list(self.ids)
        ca = list(self.cause)
        n = min(self.count, self.size)
        pos = self.pos
        out = []
        for k in range(n):
            i = (pos - n + k) % self.size
            if et[i]:
                out.append((et[i], ts[i], du[i], ar[i], ids[i], ca[i]))
        return out


class _Span:
    """One same-thread span: ``with TRACE.span(EV_X) as sp:`` (or
    ``sp.begin()`` ... ``sp.end()`` where the work is not one block).
    ``arg``, ``id`` and ``cause`` may be set before it ends."""

    __slots__ = ("_rec", "etype", "arg", "id", "cause", "t0", "_ann")

    def __init__(self, rec: "FlightRecorder", etype: int, arg: int, id_: int, cause: int):
        self._rec = rec
        self.etype = etype
        self.arg = arg
        self.id = id_
        self.cause = cause
        self._ann = None

    def __enter__(self) -> "_Span":
        if self._rec.capturing:
            import jax

            self._ann = jax.profiler.TraceAnnotation(_annotation_names[self.etype])
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        ann = self._ann
        if ann is not None:
            self._ann = None
            ann.__exit__(None, None, None)
        self._rec._put(self.etype, self.t0, t1 - self.t0, self.arg, self.id, self.cause)

    begin = __enter__

    def end(self) -> None:
        self.__exit__(None, None, None)


class _NoSpan:
    """The recorder-off scope: one shared instance, nothing recorded."""

    __slots__ = ("arg", "id", "cause", "t0")

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    begin = __enter__

    def end(self) -> None:
        pass


_NO_SPAN = _NoSpan()


class FlightRecorder:
    """The process-wide recorder. ``enabled`` is the single hot-path
    gate: with it off, :meth:`record` returns at once and :meth:`span`
    returns a shared no-op scope. ``capturing`` is raised by
    ``profiling.jax_trace`` while a profiler capture runs."""

    def __init__(self, size: int = RING_SIZE):
        self.enabled = os.environ.get("PATROL_TRACE", "1") != "0"
        self.capturing = False
        self.size = size
        self._tls = threading.local()
        self._reg_mu = threading.Lock()
        self._rings: List[_Ring] = []
        self._snap_mu = threading.Lock()
        self._snapshots: deque = deque(maxlen=4)
        self._last_anomaly: Dict[str, float] = {}

    def _ring(self) -> _Ring:
        ring = getattr(self._tls, "ring", None)
        if ring is None:
            t = threading.current_thread()
            ring = _Ring(t.ident or 0, t.name, self.size)
            self._tls.ring = ring
            with self._reg_mu:
                self._rings.append(ring)
        return ring

    def _put(self, etype: int, t_ns: int, dur_ns: int, arg: int, id_: int, cause: int) -> None:
        ring = self._ring()
        i = ring.pos
        ring.etype[i] = etype
        ring.t_ns[i] = t_ns
        ring.dur_ns[i] = dur_ns
        ring.arg[i] = arg
        ring.ids[i] = id_
        ring.cause[i] = cause
        ring.pos = (i + 1) % ring.size
        ring.count += 1

    def record(self, etype: int, dur_ns: int = 0, arg: int = 0) -> None:
        """Record one event that ends now and lasted ``dur_ns`` on the
        calling thread's ring. Lock-free: this thread is the ring's only
        writer."""
        if not self.enabled:
            return
        self._put(etype, time.perf_counter_ns() - dur_ns, dur_ns, arg, 0, 0)

    def span(self, etype: int, arg: int = 0, id: int = 0, cause: int = 0):
        """A scope around same-thread work: one ring event, and the
        ``patrol.<event>`` annotation while a capture runs."""
        if not self.enabled:
            return _NO_SPAN
        return _Span(self, etype, arg, id, cause)

    # -- dump / snapshot -----------------------------------------------------

    def dump(self) -> List[dict]:
        """All rings' live events as plain dicts (oldest-first per ring);
        ``t_ns`` is the event's start."""
        with self._reg_mu:
            rings = list(self._rings)
        out = []
        for ring in rings:
            for etype, t_ns, dur_ns, arg, id_, cause in ring.events():
                out.append(
                    {
                        "type": EVENT_NAMES.get(etype, str(etype)),
                        "t_ns": t_ns,
                        "dur_ns": dur_ns,
                        "arg": arg,
                        "id": id_,
                        "cause": cause,
                        "tid": ring.tid,
                        "thread": ring.name,
                    }
                )
        return out

    def chrome_trace(self, events: Optional[List[dict]] = None) -> bytes:
        """Chrome-trace/Perfetto JSON ('X' complete events, µs scale)."""
        evs = self.dump() if events is None else events
        trace_events = [
            {
                "name": e["type"],
                "ph": "X",
                "ts": e["t_ns"] / 1000.0,
                "dur": e["dur_ns"] / 1000.0,
                "pid": os.getpid(),
                "tid": e["tid"],
                "args": {
                    "arg": e["arg"],
                    "id": e.get("id", 0),
                    "cause": e.get("cause", 0),
                    "thread": e["thread"],
                },
            }
            for e in evs
        ]
        return json.dumps(
            {"traceEvents": trace_events, "displayTimeUnit": "ms"}
        ).encode()

    def snapshot(self, reason: str) -> Optional[dict]:
        """Freeze the current rings under a reason tag (bounded, newest
        kept). Damped to one per reason per second — an anomaly storm
        must not turn the recorder into its own hot path."""
        now = time.monotonic()
        with self._snap_mu:
            if now - self._last_anomaly.get(reason, -1e9) < 1.0:
                return None
            self._last_anomaly[reason] = now
        snap = {
            "reason": reason,
            "at_ns": time.perf_counter_ns(),
            "events": self.dump(),
        }
        with self._snap_mu:
            self._snapshots.append(snap)
        profiling.COUNTERS.inc("trace_anomaly_snapshots")
        return snap

    def snapshots(self) -> List[dict]:
        with self._snap_mu:
            return list(self._snapshots)


TRACE = FlightRecorder()


def anomaly(reason: str) -> None:
    """Anomaly hook: mark the ring and auto-snapshot it (take stall,
    convergence-budget breach, engine tick failure)."""
    if TRACE.enabled:
        TRACE.record(EV_ANOMALY, 0, 0)
    TRACE.snapshot(reason)


def clock_anchor() -> None:
    """Mark the running capture with this process's CLOCK_MONOTONIC: a
    ``patrol.clock`` annotation carrying ``mono_ns``. The annotation's
    start on the capture's timeline less ``mono_ns`` is the offset that
    maps every ring stamp (and the C++ front's ``steady_clock`` stamps,
    and ``/debug/vars`` ``mono_ns``) onto it."""
    import jax

    with jax.profiler.TraceAnnotation(
        ANNOTATION_PREFIX + "clock", mono_ns=time.monotonic_ns()
    ):
        pass


# -- cross-node take spans ---------------------------------------------------


class SpanCollector:
    """Bounded collector of completed spans (local takes + remote
    decode/merge joined by the propagated trace id). One per process —
    in-process multi-node tests see both nodes' spans here, disambiguated
    by the ``node`` field."""

    def __init__(self, cap: int = 4096):
        self._mu = threading.Lock()
        self._spans: deque = deque(maxlen=cap)

    def add(
        self,
        trace_id: int,
        node: int,
        kind: str,
        bucket: str,
        t_ns: int,
        dur_ns: int,
    ) -> None:
        with self._mu:
            self._spans.append(
                {
                    "trace_id": trace_id,
                    "node": node,
                    "kind": kind,
                    "bucket": bucket,
                    "t_ns": t_ns,
                    "dur_ns": dur_ns,
                }
            )

    def export(self, trace_id: Optional[int] = None) -> List[dict]:
        with self._mu:
            spans = list(self._spans)
        if trace_id is not None:
            spans = [s for s in spans if s["trace_id"] == trace_id]
        return spans

    def clear(self) -> None:
        with self._mu:
            self._spans.clear()


SPANS = SpanCollector()

# Take sampling: 0 = off (default), N = every Nth take gets a trace id.
_take_sample = int(os.environ.get("PATROL_TRACE_SAMPLE", "0"))
_take_counter = itertools.count(1)
# Process tag keeps ids from colliding across real multi-process nodes;
# the monotone counter keeps them unique within one process (shared by
# every in-process node).
_ID_TAG = (os.getpid() & 0x7FFF) << 48


def set_take_sampling(n: int) -> None:
    """1-in-``n`` take sampling; 0 disables. Runtime-settable (tests,
    operator resync debugging)."""
    global _take_sample
    _take_sample = max(0, int(n))


def take_sampling() -> int:
    return _take_sample


def sample_take() -> Optional[int]:
    """Next take's trace id, or None when unsampled/off. Called once per
    ticket creation; the off path is one global read + branch."""
    n = _take_sample
    if not n:
        return None
    c = next(_take_counter)
    if c % n:
        return None
    profiling.COUNTERS.inc("trace_take_samples")
    return _ID_TAG | (c & 0xFFFFFFFFFFFF)
