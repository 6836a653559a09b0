"""Device engine: the microbatching feeder between concurrent host callers
and single-device kernel calls.

The reference's concurrency story is goroutine-per-request + a mutex per
bucket + one single-threaded UDP merge loop (bucket.go:21, repo.go:54-92).
The TPU-native inversion: *batching replaces locking*. All mutation of
limiter state happens on one engine thread that drains two queues — take
tickets and replication deltas — into padded, fixed-shape kernel calls:

    submit_take()/ingest_delta()  →  queues  →  engine tick (feeder):
        fold, then merge_batch_folded / merge_rows_dense / commit_blocks
                              one scatter-max call (async dispatch)
        take_batch(groups)    one fused take call (async dispatch)
    completion pipeline (completer thread):
        read results, complete tickets, emit broadcast states

Natural batching: the feeder dispatches immediately when work exists;
requests that arrive during a device call form the next batch, so batch size
adapts to load and idle latency stays at one device round-trip. Completion
(the host-side fanout) runs on its own thread and overlaps the next tick's
device compute — see _enqueue_completion.

Hot buckets are coalesced algebraically (see ops/take.py): identical
(bucket, rate, count) tickets become one kernel row with ``nreq``; a bucket
appearing with *different* rate/count in the same tick is deferred one tick
to preserve the unique-rows kernel invariant (sequential semantics).
"""

from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from collections import deque
from functools import lru_cache
from typing import Callable, Dict, Iterable, List, NamedTuple, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from patrol_tpu.models.limiter import NANO, LimiterConfig, LimiterState, init_state
from patrol_tpu.utils import histogram as hist
from patrol_tpu.utils import profiling
from patrol_tpu.utils import trace as trace_mod
from patrol_tpu.ops import commit as commit_mod
from patrol_tpu.ops import delta as delta_ops
from patrol_tpu.ops import merge as merge_mod
from patrol_tpu.ops import wire
from patrol_tpu.ops.merge import (
    MergeBatch,
    merge_scalar_batch,
    read_rows,
    zero_rows_jit,
)
from patrol_tpu.ops.rate import Rate
from patrol_tpu.ops.take import (
    TAKE_PACK_ROWS,
    host_refill_grant_nt,
    remaining_for_request,
    split_grant,
    take_n_batch,
)
from patrol_tpu.ops import lifecycle as lifecycle_ops
from patrol_tpu.ops.gcra import GcraRequest, gcra_take_batch_jit
from patrol_tpu.ops.concurrency import ConcRequest, conc_acquire_batch_jit
from patrol_tpu.ops.hierquota import QuotaRequest, quota_take_batch_jit
from patrol_tpu.runtime.bucket import ClockFn, system_clock
from patrol_tpu.runtime.directory import (
    BucketDirectory,
    DirectoryFullError,
    OverloadedError,
)

log = logging.getLogger("patrol.engine")

# Per-tick caps: at most this many take rows / merge rows per device call;
# the rest stays queued for the next tick (the loop runs back-to-back).
MAX_TAKE_ROWS = 4096


def _take_fold_enabled() -> bool:
    """Hot-key take coalescing (rx-side fold): same-(row, rate, count)
    takes fold into ONE queue entry at submit time, so a Zipf crowd on a
    few buckets drains as a handful of take-n rows instead of eating the
    whole per-tick row budget ticket-by-ticket. Read at call time so the
    bench's per-ticket replay leg can flip it without forking the
    engine; "0" also makes _group_tickets serve one ticket per row per
    tick — the true pre-coalescing reference path."""
    return os.environ.get("PATROL_TAKE_FOLD", "1") != "0"
# Merge rows per engine tick. Bigger ticks amortize per-dispatch cost
# (decisive where each execute carries a fixed transport cost: r3-r5
# measured ~60 ms per execute regardless of kernel size) at the price of
# one compiled variant per power-of-two up to the cap; the env knob lets
# the replay bench trade warmup variants for tick size without forking
# the engine.
MAX_MERGE_ROWS = int(os.environ.get("PATROL_MAX_MERGE_ROWS", 8192))
# Device-commit pipeline (r6): how many MAX_MERGE_ROWS blocks one engine
# tick may drain and fold into a SINGLE donated commit dispatch
# (ops/commit.py). The r05 drain paid one transfer + one dispatch per
# block (~5 MB/s effective on the remote-execute transport, 18.5 s of
# ingest_device_drain_ms for 10M deltas); coalescing K blocks into one
# dispatch divides the per-dispatch constant by K and lets the staged
# transfer overlap the previous tick's compute.
#
# Default ``auto`` (device-resident ingest, r15): the feeder SIZES the
# drain per tick from the queue backlog and the completion pipeline's
# measured per-row device-commit cost — light load drains one block
# (lowest latency), floods coalesce toward the budget cap so the
# 8-KiB-interval blocks wire v2 delivers commit in as few dispatches as
# the latency budget allows. A numeric value pins the static r6
# behavior; MeshEngine pins its own static copy (fused-step drains).
_COMMIT_BLOCKS_ENV = os.environ.get("PATROL_COMMIT_BLOCKS", "auto")
COMMIT_BLOCKS_AUTO = _COMMIT_BLOCKS_ENV.strip().lower() == "auto"
COMMIT_BLOCKS = (
    4 if COMMIT_BLOCKS_AUTO else max(1, int(_COMMIT_BLOCKS_ENV))
)
# Auto-mode bounds: the widest drain auto may size, and the latency
# budget one coalesced commit dispatch may spend (the measured
# device_commit_ns EWMA caps block count so a flood can't build a
# dispatch whose completion stalls the pipeline past the budget).
COMMIT_BLOCKS_MAX = max(1, int(os.environ.get("PATROL_COMMIT_BLOCKS_MAX", 8)))
COMMIT_BUDGET_NS = int(
    float(os.environ.get("PATROL_COMMIT_BUDGET_MS", 50)) * 1e6
)
# In-flight device ticks the feeder may dispatch ahead of the completer
# (the completion-queue bound). > 1 keeps a tick queued on the device
# while the completer blocks reading the previous tick's results; the
# bound back-pressures the feeder so a slow completer can't buffer
# device results without limit.
DISPATCH_AHEAD = max(2, int(os.environ.get("PATROL_DISPATCH_AHEAD", 8)))

# patrol-fleet device-dispatch timing (ROADMAP item 1's r06 capture,
# instrumentation half): every commit/take dispatch gets a device-side
# dispatch→ready duration measured on the completion pipeline
# (block_until_ready / result-readback deltas) into the
# ``device_commit_ns``/``device_take_ns`` stage histograms plus a
# per-kernel ``device_kernel_<name>_ns`` histogram. Default on (the
# observation rides the completer thread, which blocks on device results
# anyway); opt out for overhead experiments.
DEVICE_TIMING = os.environ.get("PATROL_DEVICE_TIMING", "1") != "0"
# Kernel dispatch spans: a flight-recorder event per dispatch, and the
# ``patrol.<kernel>`` annotation while an XPlane capture runs.
_KERNEL_EVENTS = {
    k: trace_mod.event_type(k)
    for k in (
        "take_packed", "merge_folded", "merge_scalar", "commit_blocks",
        "delta_fold", "decode_fold_raw", "mesh_step",
    )
}
profiling.count_jax_compiles()


def _dispatch(kernel: str):
    """The span around one kernel dispatch."""
    return trace_mod.TRACE.span(_KERNEL_EVENTS[kernel])


def _record_take_stages(
    n: int, t_drain: int, t_ask: int, t_locked: int, t_ret: int,
    t_thunk: int, done_ns: int,
) -> None:
    """Record a device take tick's stages, one sample for each of the
    ``n`` tickets whose queue wait its drain (``t_drain``) closed. With
    ``take_queue_ns`` they tile each such ticket's ``take_service_ns``
    exactly: drain → the state lock asked for (prep: the tick's demotion,
    GC, re-route, promotions and merges, then grouping, packing and the
    ``device_put``) → held (lock wait) → the dispatch returned (dispatch)
    → the completer ran the tick's thunk (hand-off: the lock's release,
    the completion queue's back-pressure and the completer's wake) →
    ``done_ns`` (readback: the result's device wait and D2H copy, the
    staging release)."""
    hist.TAKE_PREP.record_n(t_ask - t_drain, n)
    hist.TAKE_LOCK_WAIT.record_n(t_locked - t_ask, n)
    hist.TAKE_DISPATCH.record_n(t_ret - t_locked, n)
    hist.TAKE_HANDOFF.record_n(t_thunk - t_ret, n)
    hist.TAKE_READBACK.record_n(done_ns - t_thunk, n)


BroadcastFn = Callable[[List[wire.WireState]], None]


class StagingPool:
    """Shape-bucketed reusable host staging buffers for packed device
    commits (the pinned-buffer half of the device-commit pipeline).

    ``lease()`` pops a recycled int64 buffer for a shape (or allocates on
    miss); ``release()`` returns it. The release contract is the caller's:
    a buffer may only come back once its shipped transfer is READY —
    ``jax.block_until_ready`` on the ``device_put`` result for merge
    commits (device_put copies, it never aliases the host source, so
    operand readiness means the host bytes are refillable), or the
    result readback for take ticks (compute done ⇒ operand consumed on
    any backend). Bounded per shape so a burst can't pin unbounded host
    memory."""

    __slots__ = ("_free", "_mu", "_max_per_shape")

    def __init__(self, max_per_shape: int = 8):
        self._free: Dict[tuple, list] = {}
        self._mu = threading.Lock()
        self._max_per_shape = max_per_shape

    def lease(self, shape) -> np.ndarray:
        t0 = time.perf_counter_ns()
        key = tuple(shape)
        buf = None
        with self._mu:
            stack = self._free.get(key)
            if stack:
                buf = stack.pop()
        if buf is not None:
            profiling.COUNTERS.inc("staging_reuse_hits")
        else:
            profiling.COUNTERS.inc("staging_leases_fresh")
            buf = np.empty(key, dtype=np.int64)
        hist.STAGE_STAGING_WAIT.record(time.perf_counter_ns() - t0)
        return buf

    def release(self, buf: np.ndarray) -> None:
        with self._mu:
            stack = self._free.setdefault(buf.shape, [])
            if len(stack) < self._max_per_shape:
                stack.append(buf)

# Host fast path (SURVEY §7 hard-part #1; VERDICT r3 item 1): serve
# cold/low-QPS buckets from an in-process scalar-lane model — µs-class, no
# device hop — and promote a bucket to the device path when it gets hot.
# The reference answers /take in-process in ~µs (api.go:51-86); a device
# round-trip floors a cold bucket's p99 well above that on any hardware.
HOST_FASTPATH = os.environ.get("PATROL_HOST_FASTPATH", "1") != "0"
# Promote when a bucket sees more than this many host takes (or absorbed
# rx deltas) inside one window. The default approximates the crossover
# where device batching beats per-request host python: a host take costs
# ~10-20 µs single-threaded (≈50-100k/s ceiling), so below ~40k/s per
# bucket the in-process path is strictly faster than ANY device
# round-trip; above it, coalescing thousands of requests into one kernel
# row wins. Measured on this box (BASELINE_MEASURED r3): the device path
# capped config #1 at 16.6k rps / 7.3 ms p99 while the host path holds
# it sub-ms — a low threshold demoted exactly the buckets the fast path
# exists for. Env-tunable for hosts with different single-core budgets.
HOST_PROMOTE_TAKES = int(os.environ.get("PATROL_HOST_PROMOTE_TAKES", 4096))
HOST_PROMOTE_WINDOW_NS = int(
    float(os.environ.get("PATROL_HOST_PROMOTE_WINDOW_MS", 100)) * 1e6
)
# Idle demotion (VERDICT r4 item 3): a promoted bucket whose device-path
# take rate falls below this count per demote window moves BACK to host
# residency (exact: gather the row, seed host lanes, zero the device row)
# — below the crossover the host path is strictly faster than ANY device
# round trip, and promotion was one-way in r4, so a bucket hot for one
# burst paid the device hop forever after. Hysteresis: the demote rate
# threshold sits ~8× below the promote rate (quarter the takes over twice
# the window), so residency can't flap on a steady workload.
HOST_DEMOTE_TAKES = int(
    os.environ.get("PATROL_HOST_DEMOTE_TAKES", max(HOST_PROMOTE_TAKES // 4, 1))
)
HOST_DEMOTE_WINDOW_NS = int(
    float(os.environ.get("PATROL_HOST_DEMOTE_WINDOW_MS", 200)) * 1e6
)

# Scrape mirror (patrol-dispatch stage 10, PTD003): the stats/debug
# surfaces (snapshot/snapshot_many/tokens_if_known/row_view → /metrics,
# /debug/vars, audit + anti-entropy fan-ins) used to pay one device
# gather PER CALL. The mirror keeps a host copy of the low row window,
# stamped with the (_ticks, _state_gen) epoch it reflects: while the
# epoch is unchanged the mirror is EXACT (not merely fresh-ish), so a
# steady-state scrape costs zero device transfers. Refreshes ride the
# completion pipeline when scrapes are active; a stale scrape pays one
# batched window gather instead of a targeted one.
SCRAPE_MIRROR = os.environ.get("PATROL_SCRAPE_MIRROR", "1") != "0"
SCRAPE_MIRROR_ROWS = int(os.environ.get("PATROL_SCRAPE_MIRROR_ROWS", 4096))

# Bucket lifecycle (ROADMAP item 4): idle-bucket GC on the feeder tick.
# A bound bucket whose reconstructed value equals its rate-derived refill
# (the IsZero predicate, ops/lifecycle.py) is reclaimed from the device
# plane AND the host directory — under a power-law keyspace the cold tail
# stops living forever in dense state. 0 disables the feeder cadence
# (sweeps still run via gc_sweep(): tests, bench, operators).
GC_WINDOW_NS = int(float(os.environ.get("PATROL_GC_WINDOW_MS", 500)) * 1e6)
# Only buckets untouched for this long are sweep candidates at zero
# budget pressure; pressure (and force) drops the idleness requirement —
# the predicate alone already guarantees reclaim safety, idleness just
# keeps the steady-state sweep off warm buckets.
GC_IDLE_NS = int(float(os.environ.get("PATROL_GC_IDLE_MS", 1000)) * 1e6)
# Candidate rows probed per sweep (one padded device gather).
GC_SWEEP_MAX = int(os.environ.get("PATROL_GC_SWEEP_MAX", 8192))
# Memory-budget watermarks: bound-bucket count and/or byte budget
# (0 = unenforced). Crossing soft (GC_SOFT_FRAC × budget) ramps GC
# pressure — sweeps ignore idleness and run at window/8 cadence; at the
# hard watermark admission of NEW names sheds load with an explicit
# OverloadedError (HTTP 429 "overloaded") instead of growing toward OOM.
MAX_BUCKETS = int(os.environ.get("PATROL_MAX_BUCKETS", 0))
STATE_BYTES_BUDGET = int(os.environ.get("PATROL_STATE_BYTES_BUDGET", 0))
GC_SOFT_FRAC = float(os.environ.get("PATROL_GC_SOFT_FRAC", 0.85))

# Host-side directory metadata attributable to one bound row (name bytes
# + the per-row int64/int32 columns) — the budget accounting's row class.
_ROW_HOST_BYTES = 256 + 64

# patrol-audit (net/audit.py): the admitted-token audit window. Every
# admitted take books its nanotokens into the engine's AuditLedger under
# the current window id; the audit plane gossips the closed windows'
# own-lane G-counters cluster-wide and reports the measured AP-overshoot
# factor (global admitted vs limit×1) as a live SLI. 0 = manual windows
# (tests/bench close them explicitly via roll(force=True)).
AUDIT_WINDOW_NS = int(float(os.environ.get("PATROL_AUDIT_WINDOW_MS", 5000)) * 1e6)


class AuditLedger:
    """Own-lane half of the AP-overshoot auditor: a windowed per-bucket
    admitted-token G-counter. Each admitted take books its nanotokens
    under the CURRENT window id; a window's per-bucket totals are monotone
    within the window, so they gossip as join-decompositions exactly like
    the metrics lattices (net/fleet.py) — receivers max-join per (window,
    bucket, lane). Window ids are engine-clock derived (``clock //
    window_ns``), so clock-synced nodes agree on attribution; with
    ``window_ns == 0`` windows only close via ``roll(force=True)`` and the
    id is a lockstep epoch counter (the deterministic test/bench mode).

    Alongside the admitted count the ledger keeps each bucket's limit
    view: capacity base plus the rate-derived refill over the window's
    observed span — the ``limit × 1`` denominator of the overshoot
    factor. Thread-safe; one leaf lock, never held across other locks
    (declared in analysis/race.py::GUARDS)."""

    def __init__(self, window_ns: int = 0):
        self._mu = threading.Lock()
        self.window_ns = window_ns
        self._window = 0
        self._start_ns: Optional[int] = None
        # name -> [admitted_nt, cap_nt(max), per_ns(max)] for the open window.
        self._cur: Dict[str, list] = {}
        self._closed: deque = deque(maxlen=4)
        self.windows_closed = 0

    def _clock_window(self, now: int) -> int:
        return now // self.window_ns if self.window_ns > 0 else self._window

    def _close_locked(self, now: int, next_window: int) -> None:
        start = self._start_ns if self._start_ns is not None else now
        dur = max(0, now - start)
        if self._cur:
            lanes = {
                name: (
                    v[0],
                    # limit×1: capacity base + refill over the window span.
                    v[1] + (v[1] * dur // v[2] if v[2] > 0 else 0),
                )
                for name, v in self._cur.items()
            }
            self._closed.append((self._window, dur, lanes))
            self.windows_closed += 1
        self._cur = {}
        self._window = next_window
        self._start_ns = now

    def note(
        self, name: str, admitted_nt: int, cap_nt: int, per_ns: int, now: int
    ) -> None:
        """Book one admitted take into the open window (self-rolling on
        clock-derived window ids)."""
        if admitted_nt <= 0:
            return
        with self._mu:
            if self._start_ns is None:
                self._start_ns = now
                self._window = self._clock_window(now)
            elif self.window_ns > 0:
                w = self._clock_window(now)
                if w > self._window:
                    self._close_locked(now, w)
            ent = self._cur.get(name)
            if ent is None:
                self._cur[name] = [admitted_nt, max(cap_nt, 0), max(per_ns, 0)]
            else:
                ent[0] += admitted_nt
                ent[1] = max(ent[1], cap_nt)
                ent[2] = max(ent[2], per_ns)

    def roll(self, now: int, force: bool = False) -> None:
        """Close the open window when its span lapsed (or ``force``)."""
        with self._mu:
            if self._start_ns is None:
                self._start_ns = now
                self._window = self._clock_window(now)
                return
            if force:
                self._close_locked(now, self._window + 1)
            elif self.window_ns > 0:
                w = self._clock_window(now)
                if w > self._window:
                    self._close_locked(now, w)

    def export(self):
        """→ (current window id, closed windows) where each closed window
        is ``(window_id, duration_ns, {name: (admitted_nt, limit_nt)})``
        and the OPEN window rides along too (monotone — shipping partial
        progress is join-safe). The open window's limit uses the span so
        far."""
        with self._mu:
            out = list(self._closed)
            if self._cur and self._start_ns is not None:
                # The open window's partial view (duration so far unknown
                # to a frozen clock ⇒ 0 refill, conservative).
                out.append(
                    (
                        self._window,
                        0,
                        {
                            name: (v[0], v[1])
                            for name, v in self._cur.items()
                        },
                    )
                )
            return self._window, out


class HostLanes:
    """Host-resident PN-lane state for one bucket row: the fast-path twin
    of one row of ``LimiterState`` (int64 nanotoken lanes + the elapsed
    G-counter), plus the promotion QPS window. All mutation happens under
    the engine's ``_host_mu``.

    The take arithmetic mirrors ops/take.py's ``take_batch`` step-for-step
    (itself ≙ bucket.go:186-225) for a single row with ``nreq=1`` — same
    lazy capacity base, monotonic-time guard, float64 refill grant,
    capacity cap (possibly negative ⇒ monotone forfeit booked as taken),
    conditional commit — so a bucket's observable behavior is IDENTICAL
    whether it is served here or on the device, and a later promotion join
    (lanes are monotone, max-merge) is exact, not approximate."""

    __slots__ = (
        "added", "taken", "elapsed_ns", "win_start_ns", "win_takes", "win_rx"
    )

    def __init__(self, nodes: int):
        self.added = np.zeros(nodes, np.int64)
        self.taken = np.zeros(nodes, np.int64)
        self.elapsed_ns = 0
        self.win_start_ns = 0
        self.win_takes = 0
        self.win_rx = 0  # rx deltas absorbed this window (promotion signal)

    def roll_window(self, now_ns: int) -> None:
        """Reset the promotion window when it lapsed. Both counters roll
        TOGETHER: an rx count that survived take-window rolls would accrue
        one peer echo per take and promote every clustered bucket after
        ~HOST_PROMOTE_TAKES takes total, at any QPS."""
        if now_ns - self.win_start_ns > HOST_PROMOTE_WINDOW_NS:
            self.win_start_ns = now_ns
            self.win_takes = 0
            self.win_rx = 0

    def take(
        self,
        cap_base_nt: int,
        created_ns: int,
        now_ns: int,
        rate: Rate,
        count: int,
        node_slot: int,
    ) -> Tuple[int, bool]:
        """One take; returns (remaining_tokens, ok). ≙ take_batch nreq=1."""
        cap_now_nt = rate.freq * NANO
        sum_a = int(self.added.sum())
        sum_t = int(self.taken.sum())
        tokens_nt = cap_base_nt + sum_a - sum_t

        last = min(created_ns + self.elapsed_ns, now_ns)
        delta = now_ns - last

        # The kernel's float64 refill, bit for bit (ops/take.py).
        grant_nt = host_refill_grant_nt(delta, rate.freq, rate.per_ns)
        grant_nt = min(grant_nt, cap_now_nt - tokens_nt)

        have_nt = tokens_nt + grant_nt
        count_nt = count * NANO
        if count_nt > 0:
            k = min(max(have_nt // count_nt, 0), 1)
        else:
            k = 0
        if k >= 1:
            forfeit = max(-grant_nt, 0)
            self.added[node_slot] += max(grant_nt, 0)
            self.taken[node_slot] += count_nt + forfeit
            self.elapsed_ns += delta
        return remaining_for_request(have_nt, k, count_nt, 0)


class TakeTicket:
    """One pending take request. Completion is observable both from threads
    (:meth:`wait`) and event loops (:meth:`add_done_callback`), so the
    asyncio HTTP front never blocks on the engine thread."""

    __slots__ = (
        "name",
        "row",
        "rate",
        "count",
        "now_ns",
        "_event",
        "_mu",
        "_callbacks",
        "remaining",
        "ok",
        "deferred",
        "shed",
        "t0_ns",
        "done_ns",
        "drain_ns",
        "batch",
        "trace_id",
        "sink",
        "tag",
        "stream",
    )

    def __init__(
        self, name: str, row: int, rate: Rate, count: int, now_ns: int,
        batch: int = 0,
    ):
        self.name = name
        self.row = row
        self.rate = rate
        self.count = count
        self.now_ns = now_ns
        self._event = threading.Event()
        self._mu = threading.Lock()
        self._callbacks: List[Callable[[], None]] = []
        self.remaining: int = 0
        self.ok: bool = False
        # True while re-queued by _group_tickets (rate-key conflict): such a
        # ticket is still live in the queue — failure paths must not
        # complete/unpin it (engine thread only; no lock needed).
        self.deferred = False
        # True when completed by the memory watermark's overload shed
        # (never pinned, never queued): lets the multi-take HTTP front
        # answer 429 "overloaded" for exactly the shed entries of a batch
        # while live names in the same request keep their real outcomes.
        self.shed = False
        # patrol-scope: service-latency stamps (take_service_ns is
        # done_ns - t0_ns), the native front's pump batch id (0 for other
        # callers) and the sampled cross-node trace id (None when
        # unsampled).
        self.t0_ns = time.perf_counter_ns()
        self.done_ns = 0
        # The feeder drain that closed its queue wait (take_queue_ns) and
        # opened the take stages (take_prep_ns ... take_readback_ns): 0
        # before it, and for a deferred ticket's later drain, which tiles
        # nothing.
        self.drain_ns = 0
        self.batch = batch
        self.trace_id = trace_mod.sample_take()
        # Where a native-front take's answer goes: the front's answer
        # sink, the connection tag and the h2 stream (0 = HTTP/1.1). Set
        # before the ticket can complete (submit_takes_batch's
        # ``answer``); None for every other caller.
        self.sink = None
        self.tag = 0
        self.stream = 0

    def complete(self, remaining: int, ok: bool) -> bool:
        """Returns True on the first completion (False if already done) —
        the engine unpins the ticket's directory row exactly on that
        transition. ``done_ns`` keeps a stamp its caller set first (the
        one its take_service_ns sample uses), else reads now."""
        with self._mu:
            if self._event.is_set():
                return False
            self.remaining = remaining
            self.ok = ok
            if not self.done_ns:
                self.done_ns = time.perf_counter_ns()
            self._event.set()
            callbacks, self._callbacks = self._callbacks, []
        if self.sink is not None:
            # Queued for the front; the completing code hands the batch
            # back in one native call (hand_back).
            self.sink.add(self)
        for cb in callbacks:
            cb()
        return True

    def add_done_callback(self, cb: Callable[[], None]) -> None:
        """Invoke ``cb`` once completed (immediately if already done).
        ``cb`` must be thread-safe — it runs on the engine thread."""
        with self._mu:
            if not self._event.is_set():
                self._callbacks.append(cb)
                return
        cb()

    def wait(self, timeout: Optional[float] = None) -> bool:
        ok = self._event.wait(timeout)
        if not ok:
            # A caller-visible take stall: freeze the flight recorder so
            # the tick/dispatch/completion timeline that led here is
            # inspectable after the fact (damped inside anomaly()).
            trace_mod.anomaly("take-stall")
        return ok


def _addressed(ticket: TakeTicket, answer: Optional[tuple], i: int) -> TakeTicket:
    """``ticket`` with request ``i``'s answer address from ``answer`` =
    (sink, tags, streams), or unchanged without one."""
    if answer is not None:
        ticket.sink = answer[0]
        ticket.tag = int(answer[1][i])
        ticket.stream = int(answer[2][i])
    return ticket


def hand_back(tickets: Iterable[TakeTicket]) -> None:
    """Hand the native front's answers among ``tickets``, completed just
    before, back to their front: one flush (one native call) per sink, on
    the thread that completed them. Never raises: the completing code
    still has its tick to finish (pins, audit, broadcasts), and an answer
    a flush could not send waits for the front's own flush."""
    for sink in {t.sink for t in tickets if t.sink is not None}:
        try:
            sink.flush()
        except Exception:
            log.exception("take answer hand-back failed")


class _TakeFold:
    """One coalesced take-queue entry: every ticket with the same
    (row, freq, per_ns, count) key that arrived while the entry waited
    for a tick, in arrival order. The feeder's drain counts ENTRIES
    (future packed rows), so a hot-key flood of thousands of tickets
    costs one row of the per-tick budget instead of drowning it; the
    grant still splits FIFO per ticket (ops/take.py split_grant).
    Created and appended-to only under the work condvar's lock, like
    the queue it lives in (analysis/race.py GUARDS)."""

    __slots__ = ("key", "tickets")

    def __init__(self, key: tuple, first: TakeTicket):
        self.key = key
        self.tickets = [first]


class _Delta:
    __slots__ = (
        "row", "slot", "added_nt", "taken_nt", "elapsed_ns", "scalar",
        "trace_id", "trace_name",
    )

    def __init__(
        self,
        row: int,
        slot: int,
        added_nt: int,
        taken_nt: int,
        elapsed_ns: int,
        scalar: bool = False,
    ):
        # Cross-node tracing: a sampled remote take's propagated id (and
        # the bucket name for the span label); None on the common path.
        self.trace_id = None
        self.trace_name = None
        self.row = row
        self.slot = slot
        # Ingest clamp: device state is non-negative by invariant; hostile or
        # corrupt packets must not be able to poison the max-merge.
        self.added_nt = max(added_nt, 0)
        self.taken_nt = max(taken_nt, 0)
        self.elapsed_ns = max(elapsed_ns, 0)
        # True ⇒ the delta came from a scalar-semantics (reference) peer and
        # must go through the deficit-attribution kernel (merge_scalar_batch).
        self.scalar = scalar


class _DeltaChunk:
    """A pre-vectorized batch of deltas (bulk ingest path): parallel int64
    numpy arrays, already clamped non-negative and slot-validated, plus a
    per-delta scalar-semantics flag."""

    __slots__ = ("rows", "slots", "added_nt", "taken_nt", "elapsed_ns", "scalar", "n")

    def __init__(self, rows, slots, added_nt, taken_nt, elapsed_ns, scalar=None):
        self.rows = rows
        self.slots = slots
        self.added_nt = added_nt
        self.taken_nt = taken_nt
        self.elapsed_ns = elapsed_ns
        self.scalar = (
            scalar if scalar is not None else np.zeros(len(rows), dtype=bool)
        )
        self.n = len(rows)


class DeltaArrays(NamedTuple):
    """One tick's drained replication deltas, in arrival order, as flat
    numpy arrays — the canonical form both engines consume."""

    rows: np.ndarray
    slots: np.ndarray
    added_nt: np.ndarray
    taken_nt: np.ndarray
    elapsed_ns: np.ndarray
    scalar: np.ndarray  # bool[K]: deficit-attribution (reference peer) deltas

    def __len__(self) -> int:
        return len(self.rows)


# Sentinel row for fold-padding — canonical definition lives with the
# kernels (ops/merge.py FOLD_PAD_ROW, shared with ops/commit.py); the
# underscore alias keeps this module's historical name importable.
_FOLD_PAD_ROW = merge_mod.FOLD_PAD_ROW

MAX_ROW_DENSE = 512  # padded-shape ceiling of the row-dense batch


def _pad_size(n: int, lo: int = 8, hi: int = MAX_MERGE_ROWS) -> int:
    """Next power of two ≥ n, bounded — keeps the jit-variant count ~log."""
    size = lo
    while size < n and size < hi:
        size <<= 1
    return size


def _obs_stage(h, t0_ns: int, ev: int, arg: int = 0) -> int:
    """patrol-scope stage probe: close a stage opened at ``t0_ns`` into
    its latency histogram and (when enabled) the flight recorder. The
    cost is one perf_counter read + a histogram lane increment — the
    same noise-level class as the COUNTERS mutex."""
    dur = time.perf_counter_ns() - t0_ns
    h.record(dur)
    tr = trace_mod.TRACE
    if tr.enabled:
        tr.record(ev, dur, arg)
    return dur


# Distinct-row bound for the native fold: past this the per-row lane
# blocks stop paying for themselves (the uniform shape is scatter-bound
# anyway) and the numpy fold takes over.
FOLD_NATIVE_MAX_DISTINCT = int(
    os.environ.get("PATROL_FOLD_NATIVE_MAX_DISTINCT", 4096)
)

# Per-thread reusable output buffers for the native fold (the feeder is
# the caller in production; the bench drives it from the main thread; two
# engines in one process each fold on their own feeder — thread-local
# keeps them from sharing).
_fold_tls = threading.local()


def _fold_buffers(nodes: int, cap_pairs: int):
    cached = getattr(_fold_tls, "bufs", None)
    if (
        cached is not None
        and cached[0][0] == nodes
        and cached[0][1] >= cap_pairs
    ):
        return cached[1]
    cap_pairs = 1 << max(cap_pairs - 1, 1).bit_length()  # grow-once sizes
    cap_rows = min(cap_pairs, FOLD_NATIVE_MAX_DISTINCT)
    bufs = (
        np.empty(MAX_ROW_DENSE, np.int64),
        np.empty((MAX_ROW_DENSE, nodes, 2), np.int64),
        np.empty(MAX_ROW_DENSE, np.int64),
        np.empty(cap_pairs, np.int64),
        np.empty(cap_pairs, np.int64),
        np.empty(cap_pairs, np.int64),
        np.empty(cap_pairs, np.int64),
        np.empty(cap_rows, np.int64),
        np.empty(cap_rows, np.int64),
        np.zeros(3, np.int64),
    )
    _fold_tls.bufs = ((nodes, cap_pairs), bufs)
    return bufs


def _fold_hybrid_native(deltas: DeltaArrays, nodes: int, row_dense_min: int):
    """C++ fold (pt_fold_hybrid): one hash pass into per-row lane blocks,
    threaded across cores for large batches — replaces the numpy
    lexsort+reduceat fold that dominated the hot-key tick (~6.1 ms for
    131k deltas vs ~0.2 ms of device commit, VERDICT r4 item 6). Returns
    the exact numpy-fold result shape, or None to fall back (library
    unavailable, tiny batch, or a distinct-row set past the bound)."""
    n = len(deltas.rows)
    if n < 1024:
        return None  # per-call buffers beat numpy only at batch scale
    # Cheap shape probe BEFORE any allocation or native work: a mostly-
    # distinct sample means the uniform shape (the native fold would only
    # burn a partial hash pass to discover it must bail, and the numpy
    # fold would then redo the batch from scratch). The sample is sized
    # so a clustered batch can't trip it: its unique count is bounded by
    # the true distinct-row count, so only shapes near/past the native
    # bound (where numpy is the right path anyway) read as uniform.
    sample = deltas.rows[:: max(1, n // 2048)][:2048]
    if len(np.unique(sample)) >= 0.85 * len(sample):
        return None
    from patrol_tpu import native as native_mod

    lib = native_mod.load()
    if lib is None:
        return None
    rows = np.ascontiguousarray(deltas.rows, np.int64)
    slots = np.ascontiguousarray(deltas.slots, np.int64)
    added = np.ascontiguousarray(deltas.added_nt, np.int64)
    taken = np.ascontiguousarray(deltas.taken_nt, np.int64)
    elapsed = np.ascontiguousarray(deltas.elapsed_ns, np.int64)
    bufs = _fold_buffers(nodes, min(n, FOLD_NATIVE_MAX_DISTINCT * nodes))
    (d_rows, d_upd, d_el, sp_rows, sp_slots, sp_a, sp_t, sp_er, sp_e,
     counts) = bufs
    counts[:] = 0
    rc = lib.pt_fold_hybrid(
        rows, slots, added, taken, elapsed, n, nodes, row_dense_min,
        FOLD_NATIVE_MAX_DISTINCT, d_rows, d_upd, d_el, MAX_ROW_DENSE,
        sp_rows, sp_slots, sp_a, sp_t, sp_er, sp_e, counts,
    )
    if rc != 0:
        return None
    n_pairs, n_rows, n_dense = int(counts[0]), int(counts[1]), int(counts[2])
    packed = DeviceEngine._pack_folded(
        sp_rows[:n_pairs], sp_slots[:n_pairs], sp_a[:n_pairs],
        sp_t[:n_pairs], sp_er[:n_rows], sp_e[:n_rows],
    )
    if n_dense == 0:
        return packed, None
    rp = _pad_size(n_dense, lo=8, hi=MAX_ROW_DENSE)
    rows_p = np.empty(rp, dtype=np.int64)
    rows_p[:n_dense] = d_rows[:n_dense]
    rows_p[n_dense:] = _FOLD_PAD_ROW + np.arange(rp - n_dense)
    upd_p = np.zeros((rp, nodes, 2), dtype=np.int64)
    upd_p[:n_dense] = d_upd[:n_dense]
    el_p = np.zeros(rp, dtype=np.int64)
    el_p[:n_dense] = d_el[:n_dense]
    return packed, (rows_p, upd_p, el_p)


def fold_hybrid(deltas: DeltaArrays, nodes: int, row_dense_min: int):
    """Fold-to-dense hybrid split (VERDICT r3 item 3): rows whose tick
    touches ≥ ``row_dense_min`` lanes commit their FULL lane plane as ONE
    row-window scatter update (TPU scatter is per update, window size
    free — a hot-key tick collapses from ~N updates to 1); the sparse
    remainder rides the flagged pair scatter. Returns
    (packed|None, (rows, updates, elapsed)|None); module-level so the
    bench measures the exact engine-tick computation. Large clustered
    batches fold in C++ (:func:`_fold_hybrid_native`); the numpy fold
    below is the reference implementation and the uniform-shape path."""
    native_res = _fold_hybrid_native(deltas, nodes, row_dense_min)
    if native_res is not None:
        return native_res
    ur, us, ua, ut, er, e = DeviceEngine._fold_core(deltas)
    nrow = np.empty(len(ur), bool)
    nrow[0] = True
    np.not_equal(ur[1:], ur[:-1], out=nrow[1:])
    rstart = np.flatnonzero(nrow)
    counts = np.diff(np.append(rstart, len(ur)))
    dense_sel = counts >= row_dense_min
    if not dense_sel.any():
        return DeviceEngine._pack_folded(ur, us, ua, ut, er, e), None
    di = np.flatnonzero(dense_sel)
    if len(di) > MAX_ROW_DENSE:
        # Cap the dense batch at its padded-shape ceiling; the
        # overflow rides the sparse scatter (correct, just slower).
        dense_sel = np.zeros_like(dense_sel)
        dense_sel[di[:MAX_ROW_DENSE]] = True
    pair_dense = np.repeat(dense_sel, counts)
    d_rows = er[dense_sel]  # unique + sorted (er follows ur's order)
    R = len(d_rows)
    upd = np.zeros((R, nodes, 2), dtype=np.int64)
    pr_idx = np.repeat(np.arange(R), counts[dense_sel])
    upd[pr_idx, us[pair_dense], 0] = ua[pair_dense]
    upd[pr_idx, us[pair_dense], 1] = ut[pair_dense]
    sparse = ~pair_dense
    packed = DeviceEngine._pack_folded(
        ur[sparse], us[sparse], ua[sparse], ut[sparse],
        er[~dense_sel], e[~dense_sel],
    )
    rp = _pad_size(R, lo=8, hi=MAX_ROW_DENSE)
    rows_p = np.empty(rp, dtype=np.int64)
    rows_p[:R] = d_rows
    rows_p[R:] = _FOLD_PAD_ROW + np.arange(rp - R)  # OOB, unique, sorted
    upd_p = np.zeros((rp, nodes, 2), dtype=np.int64)
    upd_p[:R] = upd
    el_p = np.zeros(rp, dtype=np.int64)
    el_p[:R] = e[dense_sel]
    return packed, (rows_p, upd_p, el_p)


# Packed-transfer variants: host↔device latency is dominated by per-array
# transfer setup (~50µs each on this stack), so the engine ships ONE
# int64[8,K] request matrix and receives ONE int64[5,K] result matrix per
# tick instead of 8 + 5 little arrays (measured: single-take p50 578µs →
# the kernel's own 38µs + one transfer each way).
@lru_cache(maxsize=8)
def _jit_take_packed(node_slot: int):
    def take_packed(state, packed):
        # The packed↔result layout lives with the kernel now
        # (ops/take.py take_n_batch — its own certified prove root);
        # this factory only binds the static node slot and donation.
        return take_n_batch(state, packed, node_slot)

    return jax.jit(take_packed, donate_argnums=0)


@lru_cache(maxsize=8)
def _jit_merge_folded():
    """Scatter-max with unique/sorted flags asserted — only valid for
    batches prepared by :meth:`DeviceEngine._fold_lane_merges`."""

    def merge_folded(state, packed):
        batch = merge_mod.FoldedMergeBatch(
            rows=packed[0].astype(jnp.int32),
            slots=packed[1].astype(jnp.int32),
            added_nt=packed[2],
            taken_nt=packed[3],
            erows=packed[4].astype(jnp.int32),
            elapsed_ns=packed[5],
        )
        return merge_mod.merge_batch_folded(state, batch)

    return jax.jit(merge_folded, donate_argnums=0)


@lru_cache(maxsize=8)
def _jit_commit_packed():
    """Coalesced block-ring commit (ops/commit.py): one int64[6, J, K]
    staged matrix → one donated dispatch folding every block. Only valid
    for matrices prepared by :func:`patrol_tpu.ops.commit.pack_commit_blocks`
    (flattened-sorted unique keys, sentinel padding)."""

    def commit_blocks(state, packed):
        blocks = commit_mod.CommitBlocks(
            rows=packed[0].astype(jnp.int32),
            slots=packed[1].astype(jnp.int32),
            added_nt=packed[2],
            taken_nt=packed[3],
            erows=packed[4].astype(jnp.int32),
            elapsed_ns=packed[5],
        )
        return commit_mod.commit_blocks(state, blocks)

    return jax.jit(commit_blocks, donate_argnums=0)


@lru_cache(maxsize=8)
def _jit_merge_rows_dense():
    """Row-window scatter-max — the dense half of the fold-to-dense
    hybrid (one update per row, full lane plane per window)."""

    def merge_rows_dense(state, rows, updates, elapsed):
        batch = merge_mod.RowDenseBatch(
            rows=rows.astype(jnp.int32),
            updates=updates,
            elapsed_ns=elapsed,
        )
        return merge_mod.merge_rows_dense(state, batch)

    return jax.jit(merge_rows_dense, donate_argnums=0)


@lru_cache(maxsize=8)
def _jit_merge_scalar_packed():
    """Deficit-attribution merge for scalar-semantics (reference-peer)
    deltas — interop path, typically a small batch."""

    def merge_scalar(state, packed):
        batch = MergeBatch(
            rows=packed[0].astype(jnp.int32),
            slots=packed[1].astype(jnp.int32),
            added_nt=packed[2],
            taken_nt=packed[3],
            elapsed_ns=packed[4],
        )
        return merge_scalar_batch(state, batch)

    return jax.jit(merge_scalar, donate_argnums=0)


class DeviceEngine:
    """Owns device state and the feeder thread. Thread-safe entry points:
    :meth:`submit_take` / :meth:`take`, :meth:`ingest_delta`,
    :meth:`snapshot`, :meth:`stop`."""

    def __init__(
        self,
        config: LimiterConfig,
        node_slot: int = 0,
        clock: ClockFn = system_clock,
        on_broadcast: Optional[BroadcastFn] = None,
        device=None,
        native_host: bool = False,
    ):
        self.config = config
        self.node_slot = node_slot
        self.clock = clock
        self.on_broadcast = on_broadcast
        # Fold-to-dense hybrid: a tick row touching at least this many
        # lanes commits its full lane plane as ONE row-window scatter
        # update instead of one update per lane — the point where the row
        # window's extra transfer bytes beat the per-update scatter cost
        # on a transfer-walled link; on a PCIe-attached chip 4 is already
        # a win.
        self._row_dense_min = max(4, config.nodes // 3)
        self.directory = BucketDirectory(config.buckets)
        self.state: LimiterState = init_state(config, device=device)

        # Profiled sync primitives: contended-acquire wait time and
        # condition park time feed the REAL /debug/pprof/mutex and /block
        # profiles (≙ runtime.SetMutexProfileFraction(50), main.go:24).
        self._cond = profiling.ProfiledCondition("engine.work")
        # Kernel calls donate the state buffers (zero-copy update); this lock
        # keeps introspection readers off a donated-and-deleted array.
        self._state_mu = profiling.ProfiledLock("engine.state")
        # Serializes evictions (pick victims → zero device rows → recycle);
        # concurrent assigners that hit a spent pool queue up behind it.
        self._evict_mu = threading.Lock()
        self._takes: deque = deque()
        self._deltas: deque = deque()
        # Hot-key coalescer index: take-fold key → its OPEN _TakeFold
        # entry in _takes (removed when the feeder drains the entry).
        # Rides the work condvar like the queue it indexes.
        self._open_folds: Dict[tuple, _TakeFold] = {}
        # Host fast path: row → HostLanes for buckets currently served
        # in-process (µs-class) instead of on-device. The bool flag array
        # gives the rx hot path an O(1)/vectorized residency probe; dict
        # and flags only ever change together, under _host_mu. This and
        # the other shared-state disciplines in this class are no longer
        # comment-level only: analysis/race.py::GUARDS registers each
        # attribute→lock pair and check.sh stage 7 (patrol-race PTR003)
        # flags any access outside the declared lock.
        self._hosted: Dict[int, HostLanes] = {}
        self._hosted_flag = np.zeros(config.buckets, dtype=bool)
        self._promote_pending: set = set()
        # Lanes popped from _hosted by a promotion drain but whose device
        # join hasn't landed yet. snapshot_planes joins this dict too, so
        # a checkpoint save in the pop→merge window still sees the lanes
        # (they'd otherwise be in NEITHER _hosted nor the device planes —
        # a restored checkpoint would drop the spend and over-admit).
        # Entries are cleared under _host_mu only AFTER the _state_mu
        # merge lands; the join is a max (idempotent), so a snapshot that
        # reads both the merged planes and a not-yet-cleared entry is
        # still exact.
        self._promoting: Dict[int, HostLanes] = {}
        self._host_mu = threading.Lock()
        # Native host-lane store (VERDICT r4 item 1): when requested and
        # the native library is available, host-resident lanes live in C++
        # blocks the HTTP front serves takes from WITHOUT crossing into
        # Python; the engine sees the same bytes through numpy-view
        # proxies, and _host_mu becomes the store's native mutex so both
        # sides serialize on one lock. Python code paths are unchanged —
        # they just operate on shared memory.
        self._native_store = None
        if native_host and HOST_FASTPATH:
            from patrol_tpu.runtime import hoststore

            # Map the injected clock onto CLOCK_REALTIME for the epoll
            # thread's takes: offset = clock() - realtime at init. Exact
            # for the CLI's offset clocks (main.go:35-37 semantics); a
            # test FakeClock driving the C++ path uses the probe's
            # explicit now instead.
            self._native_store = hoststore.NativeHostStore.create(
                nodes=config.nodes,
                node_slot=node_slot,
                directory=self.directory,
                clock_offset_ns=int(self.clock()) - time.time_ns(),
                window_ns=HOST_PROMOTE_WINDOW_NS,
            )
            if self._native_store is not None:
                self._host_mu = self._native_store.mutex()
        self._host_takes = 0  # takes served by the fast path
        self._promotions = 0  # host→device residency transitions
        self._demotions = 0  # device→host residency transitions (idle)
        # Recently-broadcast bucket names (insertion-ordered, bounded):
        # the graceful-shutdown flush re-broadcasts these buckets' FINAL
        # state so a lost last-broadcast datagram doesn't silently shed a
        # stopping node's most recent takes (tests/test_cluster.py
        # TestShutdownFlush). Names, not rows — a row may be recycled
        # between the broadcast and the flush.
        self._dirty_mu = threading.Lock()
        self._dirty_names: Dict[str, None] = {}
        self._dirty_cap = 4096
        # Idle-demotion bookkeeping (feeder-driven): rows promoted to the
        # device path and still bound, their device-take counts in the
        # current demote window, and the window's start. Set mutations run
        # under _host_mu (drain/drop) or on the feeder (_maybe_demote).
        self._promoted_rows: set = set()
        self._promoted_at: Dict[int, int] = {}  # row → promotion clock time
        self._dev_window: Dict[int, int] = {}
        self._demote_win_start: Optional[int] = None
        # Checkpoint restore pauses demotion: its flush→load→join sequence
        # must not interleave with a gather/zero that would strand the
        # restored spend in zeroed device rows (see _maybe_demote).
        self._demotion_paused = False
        # Bucket lifecycle: knobs are instance copies (tests and the soak
        # bench tune them per engine via configure_lifecycle); the sweep
        # bookkeeping below mutates under _evict_mu only (the same lock
        # that already serializes every unbind/zero/recycle path) —
        # declared in analysis/race.py::GUARDS like the rest.
        self._gc_window_ns = GC_WINDOW_NS
        self._gc_idle_ns = GC_IDLE_NS
        self._gc_sweep_max = GC_SWEEP_MAX
        self._max_buckets = MAX_BUCKETS
        self._bytes_budget = STATE_BYTES_BUDGET
        self._gc_soft_frac = GC_SOFT_FRAC
        self._gc_win_start: Optional[int] = None
        self._gc_reclaimed = 0
        self._gc_shed = 0
        self._gc_sweeps = 0
        self._gc_compactions = 0
        # Host-fastpath GC kick: takes served in-process never queue
        # work, so a pure fast-path workload would starve the feeder's
        # sweep cadence. The host-serve seams set this flag (two int
        # reads per take) at window rollover and wake the feeder, which
        # runs the sweep. Guarded by _cond like the work queues.
        self._gc_due = False
        # patrol-audit: the admitted-token window ledger (net/audit.py
        # reads it on the audit plane's pace). Known attribution gap: the
        # C++ native-front in-process takes never cross into Python, so
        # they are invisible to the ledger — audit coverage degrades to
        # the python-served paths there (documented in README).
        self._audit = AuditLedger(AUDIT_WINDOW_NS)
        if self._max_buckets or self._bytes_budget:
            from patrol_tpu.utils import slo as slo_mod

            slo_mod.SENTINEL.watch_budget(self._budget_snapshot)
        self._stopped = False
        self._busy = False
        # Tick pause (MeshEngine.resize quiesce): while True the feeder
        # parks between ticks — work queues keep absorbing submissions,
        # nothing dispatches — so device geometry (mesh/plan/step/
        # sharding) can swap atomically with NO tick in flight. Guarded
        # by _cond like the work queues; _stopped overrides it so a
        # shutdown never deadlocks behind a forgotten pause.
        self._tick_paused = False
        self._ticks = 0  # device calls issued (observability)
        # Device-state mutations that do NOT ride a _ticks bump (row
        # zeroing on evict/demote/reclaim, the gcra/conc/quota
        # microbatches). (_ticks, _state_gen) together form the scrape
        # epoch: any device-state change moves it, so an epoch-matched
        # mirror read is exactly the gather it replaces.
        self._state_gen = 0
        # (epoch, pn[K,N,2], elapsed[K]) or None — swapped atomically as
        # one tuple so readers never see torn pn/elapsed/epoch combos.
        self._scrape_mirror: Optional[Tuple[Tuple[int, int], np.ndarray, np.ndarray]] = None
        self._mirror_window = (
            min(int(config.buckets), SCRAPE_MIRROR_ROWS)
            if SCRAPE_MIRROR
            else 0
        )
        self._mirror_want = False  # a scrape went stale; completer refreshes
        # Cross-node tracing: (trace_id, bucket) pairs drained into the
        # current tick; the feeder records their merge spans after _apply.
        self._tick_traced: List[Tuple[int, str]] = []
        self._evictions = 0  # rows recycled under pool pressure
        self._scalar_dropped = 0  # v1 deltas dropped for unknown capacity
        # Completion pipeline: the feeder DISPATCHES device ticks and hands
        # (thunk, tickets) to this queue; the completer thread blocks on
        # the device result (np.asarray) and fans results out to tickets.
        # Host-side completion work (result read, per-ticket fanout, wire
        # encode for broadcasts) therefore overlaps the NEXT tick's device
        # compute instead of serializing with it — on TPU the device step
        # is ~28 µs while completion is comparable-or-larger Python time,
        # so the overlap roughly doubles sustained tick rate. Bounded so a
        # slow completer back-pressures the feeder instead of buffering
        # unboundedly.
        self._pcond = profiling.ProfiledCondition("engine.completion")
        self._pending: deque = deque()
        self._completing = False
        self._feeder_done = False
        # Device-commit pipeline: reusable staging buffers for the packed
        # commit/take matrices (shipped with jax.device_put BEFORE the
        # state lock so transfer overlaps the previous tick's compute),
        # and the dispatch-ahead bound on in-flight device ticks.
        self._staging = StagingPool()
        # The take_service_ns histogram this engine's completions record
        # into (bound once, so a test's engine can be given its own).
        self._take_service = hist.TAKE_SERVICE
        self._dispatch_ahead = DISPATCH_AHEAD
        # Adaptive commit-block sizing (PATROL_COMMIT_BLOCKS=auto):
        # measured per-row device-commit cost (completer-written racy
        # float gauge) and the feeder's current drain width. Starts at
        # the static default so warmup compiles the same shape ladder;
        # the first ticks then track the backlog.
        self._commit_row_ns_ewma = 0.0
        # Materialize the class default as an instance attr: auto mode
        # mutates it per tick, and the class constant must stay pristine
        # for the next engine.
        self._commit_blocks = type(self)._commit_blocks
        self._completer = threading.Thread(
            target=self._complete_loop, name="patrol-engine-complete", daemon=True
        )
        self._completer.start()
        self._thread = threading.Thread(target=self._run, name="patrol-engine", daemon=True)
        self._thread.start()

    # -- eviction (the dynamic-keyspace story; VERDICT r1 item 3) -----------

    def _evict(self, need: int) -> int:
        """Reclaim at least ``need`` rows: unbind the LRU unpinned rows,
        zero their device state in one batch, recycle the slots. Evicts a
        swath per trip so pool-exhaustion doesn't thrash. Caller must hold
        ``_evict_mu``. Returns rows reclaimed (0 ⇒ everything is pinned)."""
        # A fraction of the pool per trip: big enough to amortize the device
        # zeroing call, small enough that recently-used buckets survive.
        swath = min(4096, max(1, self.config.buckets // 8))
        victims = self.directory.pick_victims(max(need, swath))
        if victims.size == 0:
            return 0
        # Unbound now; forget any host-resident lanes BEFORE the rows
        # recycle, or a re-bind would inherit a dead bucket's state.
        self._drop_hosted_rows(victims)
        k = _pad_size(int(victims.size), lo=8, hi=1 << 20)
        rows = np.full(k, victims[0], np.int32)  # pad dupes: zeroing twice is fine
        rows[: victims.size] = victims
        with self._state_mu:
            self.state = zero_rows_jit(self.state, jnp.asarray(rows))
            self._state_gen += 1
        self.directory.recycle(victims)
        self._evictions += int(victims.size)
        log.info("evicted %d idle buckets (pool pressure)", victims.size)
        return int(victims.size)

    def _with_evict_retry(self, call, need: int):
        """Second-chance eviction scaffolding shared by every assign
        variant: fast path, then evict-and-retry under ``_evict_mu``.
        Loops because concurrent fast-path assigners may consume freed
        rows before the re-try; each iteration that evicts makes global
        progress. Returns None when every row is mid-flight (nothing
        evictable)."""
        try:
            return call()
        except DirectoryFullError:
            pass
        with self._evict_mu:
            while True:
                try:
                    return call()
                except DirectoryFullError:
                    if self._evict(need) == 0:
                        return None

    def assign_row(self, name: str, now: int, pin: bool = False) -> Tuple[int, bool]:
        """Directory assign with second-chance eviction on a spent pool.
        Raises DirectoryFullError only when every row is mid-flight."""
        res = self._with_evict_retry(
            lambda: self.directory.assign(name, now, pin=pin), 1
        )
        if res is None:
            raise DirectoryFullError("every bucket row is mid-flight")
        row, fresh = res
        # Unpinned (introspection) creations re-seed here; the take path
        # (pin=True, submit_take) pops the tombstone itself so it can
        # write the seed into fresh HOST lanes before the first commit.
        if fresh and not pin and self.directory.has_tombstones():
            seed = self._pop_tombstone_seed(name, row)
            if seed is not None:
                with self._cond:
                    self._deltas.append(_Delta(row, self.node_slot, *seed))
                    self._cond.notify()
        return res

    def _assign_pinned(self, name: str, now: int) -> Tuple[int, bool]:
        return self.assign_row(name, now, pin=True)

    def _assign_many_pinned(
        self, names: Sequence[str], now: int, hashes=None, with_fresh=False
    ):
        """Batch form of :meth:`_assign_pinned`; returns rows (or
        ``(rows, bind_fresh_mask)`` with ``with_fresh``), or None when
        the pool is spent with every row pinned (callers drop the batch —
        replication is loss-tolerant)."""
        return self._with_evict_retry(
            lambda: self.directory.assign_many(
                names, now, pin=True, hashes=hashes, with_fresh=with_fresh
            ),
            len(names),
        )

    def _assign_many_pinned_wire(self, names, name_rows, name_lens, hashes, now):
        """Wire-decoded variant of :meth:`_assign_many_pinned` — fresh
        binds copy the already-decoded name bytes vectorized
        (directory.assign_many_wire); same eviction-retry contract."""
        return self._with_evict_retry(
            lambda: self.directory.assign_many_wire(
                names, name_rows, name_lens, hashes, now, pin=True
            ),
            len(names),
        )

    # -- bucket lifecycle: idle-bucket GC + memory budget (ROADMAP item 4)

    def configure_lifecycle(
        self,
        window_ms: Optional[float] = None,
        idle_ms: Optional[float] = None,
        sweep_max: Optional[int] = None,
        max_buckets: Optional[int] = None,
        bytes_budget: Optional[int] = None,
        soft_frac: Optional[float] = None,
    ) -> None:
        """Tune the lifecycle knobs on a live engine (tests, the soak
        bench, operators). Setting a budget registers this engine with
        the SLO sentinel so watermark breaches auto-fire flight-recorder
        anomaly snapshots."""
        if window_ms is not None:
            self._gc_window_ns = int(window_ms * 1e6)
        if idle_ms is not None:
            self._gc_idle_ns = int(idle_ms * 1e6)
        if sweep_max is not None:
            self._gc_sweep_max = sweep_max
        if max_buckets is not None:
            self._max_buckets = max_buckets
        if bytes_budget is not None:
            self._bytes_budget = bytes_budget
        if soft_frac is not None:
            self._gc_soft_frac = soft_frac
        if self._max_buckets or self._bytes_budget:
            from patrol_tpu.utils import slo as slo_mod

            slo_mod.SENTINEL.watch_budget(self._budget_snapshot)

    def state_bytes_in_use(self) -> int:
        """Bytes of limiter state attributable to live buckets: device
        row planes (pn + elapsed), host directory metadata, host-resident
        lanes, and GC tombstones — the ``/debug/vars`` accounting the
        byte budget enforces against."""
        n = self.config.nodes
        row_bytes = n * 16 + 8 + _ROW_HOST_BYTES
        _t_n, t_bytes = self.directory.tombstone_stats()
        return (
            len(self.directory) * row_bytes
            + len(self._hosted) * (n * 16 + 64)
            + t_bytes
        )

    def _budget_pressure(self) -> int:
        """0 = under budget, 1 = soft watermark (GC pressure ramp),
        2 = hard watermark (new-name admission sheds)."""
        hard = soft = False
        if self._max_buckets:
            bound = len(self.directory)
            hard |= bound >= self._max_buckets
            soft |= bound >= int(self._max_buckets * self._gc_soft_frac)
        if self._bytes_budget:
            in_use = self.state_bytes_in_use()
            hard |= in_use >= self._bytes_budget
            soft |= in_use >= int(self._bytes_budget * self._gc_soft_frac)
        return 2 if hard else (1 if soft else 0)

    def _budget_snapshot(self) -> dict:
        """The SLO sentinel's budget provider: breach ⇒ anomaly snapshot
        (utils/slo.py watch_budget)."""
        return {
            "state_bytes_in_use": self.state_bytes_in_use(),
            "state_bytes_budget": self._bytes_budget,
            "buckets_bound": len(self.directory),
            "max_buckets": self._max_buckets,
            "over": self._budget_pressure() >= 2,
        }

    def _shed_new_names(self, now: int, n: int = 1) -> bool:
        """Hard-watermark admission check for NEW bucket names: one
        emergency sweep (damped to window/8 cadence) gets a chance to
        free budget; if pressure holds, the caller sheds the admission
        with an explicit signal instead of growing state. Existing names
        are never shed — their state is already paid for."""
        if self._budget_pressure() < 2:
            return False
        start = self._gc_win_start
        if start is None or now - start > self._gc_window_ns // 8:
            self.gc_sweep(now, force=True)
            if self._budget_pressure() < 2:
                return False
        with self._evict_mu:
            self._gc_shed += n
        profiling.COUNTERS.inc("gc_pressure_shed", n)
        trace_mod.anomaly("budget-shed")
        return True

    def _kick_gc_if_due(self, now: int) -> None:
        """Host-fastpath seam: wake the feeder for a sweep when the GC
        window rolled over (in-process takes never queue feeder work, so
        without this a pure fast-path workload never collects). Cost on
        the serve path: two int reads; the sweep itself runs on the
        feeder."""
        if not self._gc_window_ns:
            return
        start = self._gc_win_start
        if start is not None and now - start <= self._gc_window_ns:
            return
        with self._cond:
            self._gc_due = True
            self._cond.notify()

    def _maybe_gc(self) -> None:
        """Feeder-tick lifecycle cadence: sweep at window rollover, or at
        window/8 under budget pressure (the graceful-degradation ramp —
        GC ramps first, only then does admission shed)."""
        if not self._gc_window_ns:
            return
        now = self.clock()
        start = self._gc_win_start
        if start is None:
            with self._evict_mu:
                self._gc_win_start = now
            return
        window = self._gc_window_ns
        if (self._max_buckets or self._bytes_budget) and self._budget_pressure():
            window //= 8
        if now - start > window:
            self.gc_sweep(now)

    def gc_sweep(self, now_ns: Optional[int] = None, force: bool = False) -> int:
        """One lifecycle sweep: probe up to ``_gc_sweep_max`` idle
        candidates through the IsZero kernel (ops/lifecycle.py — host
        lanes answer via the numpy twin without a device hop), reclaim
        the full ones from the device plane and the host directory, and
        compact the free list. Returns buckets reclaimed. Callable from
        any thread: every candidate's verdict is re-verified under
        ``_evict_mu`` by :meth:`BucketDirectory.reclaim_rows` (pins and
        an untouched ``last_used_ns`` stamp), so in-flight takes/deltas —
        and rows that saw traffic after the probe — void their reclaim.

        Conservation (the part the provers pin): the reclaimed bucket's
        own PN lane + refill clock go into a directory tombstone and
        re-seed the row on re-creation, so the own-lane G-counters stay
        monotone across reclaim epochs — a peer's stale echo of the old
        lane values can never absorb (erase) post-reclaim spend. The
        protocol model's ``gc-drops-admitted-tokens`` mutation is exactly
        this design with the tombstone dropped, and it is rejected."""
        now = self.clock() if now_ns is None else now_ns
        pressure = self._budget_pressure()
        idle_ns = 0 if (force or pressure) else self._gc_idle_ns
        t0 = time.perf_counter_ns()
        with trace_mod.TRACE.span(trace_mod.EV_GC_SWEEP) as sp:
            cands, stamps = self.directory.gc_candidates(
                now, idle_ns, self._gc_sweep_max
            )
            reclaimed = 0
            if cands.size:
                reclaimed = self._gc_reclaim(cands, stamps, now)
            sp.arg = reclaimed
        with self._evict_mu:
            self._gc_sweeps += 1
            self._gc_win_start = now
        profiling.COUNTERS.inc("gc_sweeps")
        profiling.COUNTERS.set_max(
            "state_bytes_in_use", self.state_bytes_in_use()
        )
        hist.GC_SWEEP.record(time.perf_counter_ns() - t0)
        return reclaimed

    def _gc_reclaim(self, cands: np.ndarray, stamps: np.ndarray, now: int) -> int:
        """Probe + reclaim body of :meth:`gc_sweep`."""
        n = len(cands)
        cap = self.directory.cap_base_nt[cands]
        per = self.directory.rate_per_ns[cands]
        created = self.directory.created_ns[cands]
        full = np.zeros(n, bool)
        own_a = np.zeros(n, np.int64)
        own_t = np.zeros(n, np.int64)
        el = np.zeros(n, np.int64)
        # Rows mid-promotion live in NEITHER plane completely (lanes
        # popped, device join not landed): never probe or reclaim them.
        # A promotion requested after this snapshot is caught by the
        # reclaim's last_used stamp — the takes that triggered it
        # refreshed the row at assign.
        with self._host_mu:
            promo = set(self._promote_pending) | set(self._promoting)
            hosted_sel = self._hosted_flag[cands].copy()
        if promo:
            keep = np.array([int(r) not in promo for r in cands], bool)
        else:
            keep = np.ones(n, bool)
        host_idx = np.flatnonzero(hosted_sel & keep)
        if host_idx.size:
            with self._host_mu:
                for i in host_idx:
                    lanes = self._hosted.get(int(cands[i]))
                    if lanes is None:
                        continue
                    held = (lanes.added != 0) | (lanes.taken != 0)
                    held[self.node_slot] = False
                    sa = int(lanes.added.sum())
                    st = int(lanes.taken.sum())
                    full[i] = bool(
                        lifecycle_ops.host_lifecycle_full(
                            sa, st, lanes.elapsed_ns, cap[i], created[i],
                            now, per[i], held.any(),
                        )
                    )
                    own_a[i] = int(lanes.added[self.node_slot])
                    own_t[i] = int(lanes.taken[self.node_slot])
                    el[i] = lanes.elapsed_ns
        dev_idx = np.flatnonzero(~hosted_sel & keep)
        if dev_idx.size:
            m = len(dev_idx)
            k = _pad_size(m, lo=8, hi=1 << 20)
            rows_p = np.zeros(k, np.int32)
            rows_p[:m] = cands[dev_idx]
            pad = np.zeros(k, np.int64)

            def col(vals):
                out = pad.copy()
                out[:m] = vals
                return jnp.asarray(out)

            probe = lifecycle_ops.LifecycleProbe(
                rows=jnp.asarray(rows_p),
                now_ns=col(np.full(m, now, np.int64)),
                per_ns=col(per[dev_idx]),
                cap_base_nt=col(cap[dev_idx]),  # padding keeps cap 0 ⇒ never full
                created_ns=col(created[dev_idx]),
            )
            with self._state_mu:
                view = lifecycle_ops.lifecycle_probe_jit(
                    self.state, probe, self.node_slot
                )
            # One batched, padded probe readback per GC sweep: the host
            # must learn which rows are reclaimable — cadenced by the
            # sweep interval, never per-request.
            full[dev_idx] = np.asarray(view.full)[:m]  # patrol-lint: disable=PTD003
            own_a[dev_idx] = np.asarray(view.own_added_nt)[:m]  # patrol-lint: disable=PTD003
            own_t[dev_idx] = np.asarray(view.own_taken_nt)[:m]  # patrol-lint: disable=PTD003
            el[dev_idx] = np.asarray(view.elapsed_ns)[:m]  # patrol-lint: disable=PTD003
        vict = np.flatnonzero(full)
        if not vict.size:
            return 0
        with self._evict_mu:
            kept = self.directory.reclaim_rows(
                cands[vict],
                stamps[vict],
                [(own_a[i], own_t[i], el[i]) for i in vict],
            )
            if not kept.size:
                return 0
            self._drop_hosted_rows(kept)
            k = _pad_size(int(kept.size), lo=8, hi=1 << 20)
            rows_z = np.full(k, kept[0], np.int32)
            rows_z[: kept.size] = kept
            with self._state_mu:
                self.state = zero_rows_jit(self.state, jnp.asarray(rows_z))
                self._state_gen += 1
            if self.directory.recycle_compact(kept):
                self._gc_compactions += 1
                profiling.COUNTERS.inc("directory_compactions")
            self._gc_reclaimed += int(kept.size)
        profiling.COUNTERS.inc("gc_buckets_reclaimed", int(kept.size))
        log.debug("lifecycle GC reclaimed %d full idle buckets", kept.size)
        return int(kept.size)

    def _pop_tombstone_seed(self, name: str, row: int):
        """Consume a reclaimed bucket's tombstone at re-creation:
        → (own_added_nt, own_taken_nt, elapsed_ns) or None. Restores the
        row's original creation stamp so the refill clock reconstructs
        exactly. The seed MUST land before the row's first take commit
        (callers order it into the same tick's merge phase, or write it
        straight into fresh host lanes) — a later join would let the
        tombstone values absorb the first takes' debits."""
        tomb = self.directory.pop_tombstone(name, row)
        if tomb is None:
            return None
        return tomb[0], tomb[1], tomb[2]

    def _reseed_fresh_rows(self, names, rows, fresh_mask) -> None:
        """Bulk-ingest tail: queue tombstone seeds for freshly-bound rows
        (merge order against the triggering deltas is free — joins
        commute)."""
        if not self.directory.has_tombstones():
            return
        seeds = []
        seen = set()
        for i in np.flatnonzero(fresh_mask):
            row = int(rows[i])
            if row in seen:
                continue
            seen.add(row)
            seed = self._pop_tombstone_seed(names[i], row)
            if seed is not None:
                seeds.append(_Delta(row, self.node_slot, *seed))
        if seeds:
            with self._cond:
                self._deltas.extend(seeds)
                self._cond.notify()

    def device_vars(self) -> Dict[str, object]:
        """What this engine runs on, for /debug/vars: the backend as JAX
        reports it, its ingest kernel, and each device's peak memory
        (None where the backend keeps no stats, as the CPU)."""
        from patrol_tpu.utils import compile_cache

        devs = jax.devices()
        mem = [d.memory_stats() or {} for d in devs]
        return {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
            "jax": jax.__version__,
            "compile_cache_dir": compile_cache.cache_dir(),
            "ingest_kernel": (
                "decode_fold_raw (xla)"
                if self._raw_ingest_capable
                else "host decode + merge"
            ),
            "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        }

    def lifecycle_stats(self) -> Dict[str, object]:
        """The bucket-lifecycle accounting block for ``/debug/vars`` and
        the soak receipts (live gauges; the CounterRegistry carries the
        cluster-mergeable monotone counters next to these)."""
        t_n, _t_bytes = self.directory.tombstone_stats()
        return {
            "engine_gc_reclaimed": self._gc_reclaimed,
            "engine_gc_shed": self._gc_shed,
            "engine_gc_sweeps": self._gc_sweeps,
            "engine_gc_compactions": self._gc_compactions,
            "engine_gc_tombstones": t_n,
            "engine_state_bytes": self.state_bytes_in_use(),
            "engine_state_bytes_budget": self._bytes_budget,
            "engine_max_buckets": self._max_buckets,
            "engine_buckets_bound": len(self.directory),
            "engine_budget_pressure": self._budget_pressure(),
        }

    # -- entry points -------------------------------------------------------

    def _enqueue_take_locked(self, ticket: TakeTicket) -> None:
        """Queue one take (caller holds ``_cond``). With the hot-key fold
        on, a ticket whose (row, rate, count) key already has an OPEN
        queue entry rides that entry instead of appending its own — the
        rx-side collapse that keeps a single-name flood at one row of
        the per-tick budget."""
        if _take_fold_enabled():
            key = (
                ticket.row,
                ticket.rate.freq,
                ticket.rate.per_ns,
                ticket.count,
            )
            fold = self._open_folds.get(key)
            if fold is not None:
                fold.tickets.append(ticket)
                profiling.COUNTERS.inc("take_tickets_folded")
                return
            fold = _TakeFold(key, ticket)
            self._open_folds[key] = fold
            self._takes.append(fold)
            return
        self._takes.append(ticket)

    def submit_take(
        self, name: str, rate: Rate, count: int, now_ns: Optional[int] = None
    ) -> Tuple[TakeTicket, bool]:
        """Queue a take; returns (ticket, created). ``created`` mirrors the
        get-or-create miss signal that triggers incast (repo.go:96-106).
        Raises :class:`OverloadedError` for a NEW name when the memory
        budget's hard watermark holds after an emergency GC sweep — the
        explicit 429-class shed signal of the lifecycle layer."""
        now = self.clock() if now_ns is None else now_ns
        if (
            (self._max_buckets or self._bytes_budget)
            and self.directory.lookup(name) is None
            and self._shed_new_names(now)
        ):
            raise OverloadedError(
                "memory budget spent and nothing reclaimable; "
                f"new bucket {name!r} shed"
            )
        row, fresh = self._assign_pinned(name, now)
        seed = self._pop_tombstone_seed(name, row) if fresh else None
        # First *local* take on the row (capacity still unset) counts as a
        # miss for incast purposes even when replication created the row
        # first: scalar (v1-peer) deltas are dropped while the capacity is
        # unknown, so peer state must be re-solicited now that it is.
        created = fresh or int(self.directory.cap_base_nt[row]) == 0
        self.directory.init_cap_base(row, rate.freq * NANO)
        self.directory.note_rate(row, rate.per_ns)
        ticket = TakeTicket(name, row, rate, count, now)
        if (
            HOST_FASTPATH
            and (fresh or self._hosted_flag[row])
            and self._host_serve_ticket(ticket, fresh, seed=seed)
        ):
            self._kick_gc_if_due(now)
            return ticket, created
        with self._cond:
            if seed is not None:
                # Tombstone re-seed rides the SAME tick's merge phase —
                # merges apply before takes, so the first take commits on
                # top of the restored own lane, never below it.
                self._deltas.append(_Delta(row, self.node_slot, *seed))
            self._enqueue_take_locked(ticket)
            self._cond.notify()
        return ticket, created

    # -- host fast path (cold/low-QPS buckets; VERDICT r3 item 1) -----------

    def _host_serve_ticket(
        self,
        ticket: TakeTicket,
        fresh: bool,
        out_broadcasts: Optional[List[wire.WireState]] = None,
        seed: Optional[Tuple[int, int, int]] = None,
    ) -> bool:
        """Complete an existing ticket from the host lane model; False ⇒
        the row is device-resident and the caller keeps the device path.
        Promotion to the device path happens here when the bucket's QPS
        window crosses HOST_PROMOTE_TAKES. ``out_broadcasts``: batch
        callers pass an accumulator so a whole batch fans out through ONE
        on_broadcast call, like the device completion path.

        Known creation race, accepted by design: between the directory
        bind and the hosted-flag flip (sub-µs of straight-line python), a
        concurrent rx delta or a concurrent take on the SAME brand-new
        name can route to the device plane, which the host model doesn't
        read. Consequences, both bounded to one bucket creation: (a) that
        spend is invisible to host admission until promotion joins the
        planes — at most one bucket burst of over-admission; (b) for a
        leaked concurrent TAKE, the promotion max-join keeps the larger
        of the two own-lane debits instead of their sum, i.e. the smaller
        take can be uncounted. Class precedent: the reference's merge
        loses concurrent takes across nodes the same way by design
        (scalar max, SURVEY §2 known-bugs) and accepts seconds-scale
        multiplied admission under partition (README.md:64-76); this
        window is ~6 orders of magnitude narrower. Closing it fully needs
        bind+host atomicity across the directory and host locks, whose
        ordering would deadlock against eviction (_evict holds _evict_mu
        then takes _host_mu via _drop_hosted_rows)."""
        row, rate, now = ticket.row, ticket.rate, ticket.now_ns
        with self._host_mu:
            lanes = self._hosted.get(row)
            if lanes is None:
                if not fresh:
                    return False  # promoted by a concurrent rx/take
                if self._native_store is not None:
                    # C++-backed block (we hold _host_mu == store mutex):
                    # from here the epoll thread serves this row in-front.
                    lanes = self._native_store.host_locked(row)
                else:
                    lanes = HostLanes(self.config.nodes)
                if seed is not None:
                    # Tombstone re-seed (lifecycle GC): the fresh lanes
                    # resume at the reclaimed bucket's own-lane values
                    # BEFORE the first take commits, so stale peer echoes
                    # can never absorb post-reclaim spend.
                    lanes.added[self.node_slot] = seed[0]
                    lanes.taken[self.node_slot] = seed[1]
                    lanes.elapsed_ns = seed[2]
                self._hosted[row] = lanes
                self._hosted_flag[row] = True
            lanes.roll_window(now)
            lanes.win_takes += 1
            # cap is read HERE, while the caller's pin still protects the
            # row — after the unpin below an eviction could re-bind the
            # row and a late read would broadcast another bucket's
            # capacity into peers' monotone lanes (permanently).
            cap = int(self.directory.cap_base_nt[row])
            remaining, ok = lanes.take(
                cap,
                int(self.directory.created_ns[row]),
                now,
                rate,
                ticket.count,
                self.node_slot,
            )
            self._host_takes += 1
            own_a = int(lanes.added[self.node_slot])
            own_t = int(lanes.taken[self.node_slot])
            sum_a = int(lanes.added.sum())
            sum_t = int(lanes.taken.sum())
            elapsed = lanes.elapsed_ns
            if lanes.win_takes > HOST_PROMOTE_TAKES:
                self._promote_locked(row)
        done_ns = ticket.done_ns = time.perf_counter_ns()
        if ticket.complete(remaining, ok):
            self.directory.unpin_rows([row])
        self._take_service.record(done_ns - ticket.t0_ns)
        if ok:
            # patrol-audit: book the admitted tokens into the open audit
            # window (the AP-overshoot auditor's own lane). Leaf lock,
            # taken strictly after _host_mu released.
            self._audit.note(
                ticket.name, ticket.count * NANO, cap, rate.per_ns, now
            )
        if ticket.trace_id:
            trace_mod.SPANS.add(
                ticket.trace_id, self.node_slot, "take", ticket.name,
                ticket.t0_ns, done_ns - ticket.t0_ns,
            )
            tr = trace_mod.TRACE
            if tr.enabled:
                tr.record(trace_mod.EV_TAKE, done_ns - ticket.t0_ns, 1)
        # Replicate exactly as the device completion does (zero state is
        # the incast request marker and must never broadcast).
        if (own_a or own_t or elapsed or cap) and self.on_broadcast is not None:
            ws = wire.from_nanotokens(
                ticket.name, cap + sum_a, sum_t, elapsed,
                origin_slot=self.node_slot, cap_nt=cap,
                lane_added_nt=own_a, lane_taken_nt=own_t,
                trace_id=ticket.trace_id,
            )
            if out_broadcasts is not None:
                out_broadcasts.append(ws)
            else:
                self._emit_broadcasts([ws])
        return True

    def _emit_broadcasts(self, broadcasts: List[wire.WireState]) -> None:
        if not broadcasts:
            return
        self._note_dirty(broadcasts)
        if self.on_broadcast is not None:
            try:
                self.on_broadcast(broadcasts)
            except Exception:  # pragma: no cover
                log.exception("broadcast hook failed")

    def _note_dirty(self, broadcasts: List[wire.WireState]) -> None:
        """Remember which buckets this node broadcast state for (bounded,
        newest kept) — the shutdown-flush working set. Also stamps the
        patrol-audit per-bucket emission clock (staleness sampler)."""
        now = self.clock()
        with self._dirty_mu:
            d = self._dirty_names
            for st in broadcasts:
                d.pop(st.name, None)  # move-to-back keeps recency order
                d[st.name] = None
            while len(d) > self._dirty_cap:
                d.pop(next(iter(d)))
        for st in broadcasts:
            row = self.directory.lookup(st.name)
            if row is not None:
                self.directory.last_emit_ns[row] = now

    def drain_dirty_states(self, limit: int = 1024) -> List[wire.WireState]:
        """Snapshot the most recently broadcast buckets' CURRENT full lane
        state and clear the dirty set — the graceful-shutdown flush
        payload. Bounded by ``limit`` buckets (newest first); per-lane
        states, so both replication backends ship them on the normal
        broadcast path."""
        with self._dirty_mu:
            names = list(self._dirty_names)[-limit:]
            self._dirty_names.clear()
        out: List[wire.WireState] = []
        for lo in range(0, len(names), 64):
            for states in self.snapshot_many(names[lo : lo + 64]).values():
                out.extend(states)
        return out

    def _promote_locked(self, row: int) -> None:
        """Mark a bucket for promotion to device residency. The row KEEPS
        serving host-side (flag stays set, lanes stay live) until the
        feeder's next :meth:`_drain_promotions` joins every pending row's
        lanes in ONE batched device merge — deferral means no device
        round trip ever runs under ``_host_mu`` (a synchronous join here
        stalled every hosted bucket for the call; on a remote-compile
        transport that was an ~80 ms cliff on unrelated buckets), and the
        tick-ordered drain (pop+flip, then join, then _apply) preserves
        the atomicity argument: a take can only route device-ward AFTER
        the flag flips, and by then the join for its tick has landed.
        Caller holds ``_host_mu`` (a declared HOLDER contract in
        analysis/race.py — patrol-race checks this body as if the lock
        were taken at entry)."""
        if row in self._hosted:
            self._promote_pending.add(row)
            with self._cond:
                self._cond.notify()

    def _drain_promotions(self) -> None:
        """Complete pending host→device promotions: pop lanes + flip flags
        under ``_host_mu`` (brief), then apply ONE padded merge per
        MAX_MERGE_ROWS chunk under ``_state_mu``. Callers: the FEEDER at
        tick start (before _apply, so same-tick device work sees the
        joined planes — the ordering the promotion design relies on) and
        :meth:`flush_hosted` only on a STOPPED engine; a live off-feeder
        drain could flip flags and lose the join/apply ordering race.

        The whole pop→merge window runs under ``_evict_mu``: an eviction
        (or release) landing between the pop and the merge would zero and
        recycle the device row, and the already-packed merge would then
        resurrect the dead bucket's lanes into whatever bucket is bound
        to the recycled row next. ``_evict_mu`` is taken strictly outside
        ``_host_mu``/``_state_mu`` everywhere (same order as _evict and
        release_bucket), so this adds no ordering cycle."""
        with self._host_mu:
            if not self._promote_pending:
                return
        with self._evict_mu:
            self._drain_promotions_locked()

    def _drain_promotions_locked(self) -> None:
        """Body of :meth:`_drain_promotions`; caller holds ``_evict_mu``."""
        with self._host_mu:
            if not self._promote_pending:
                return
            popped: List[Tuple[int, HostLanes]] = []
            for row in self._promote_pending:
                lanes = self._hosted.pop(row, None)
                self._hosted_flag[row] = False
                if self._native_store is not None:
                    # Stop in-front serving NOW, inside the same critical
                    # section that flips the Python flag (the block's data
                    # stays valid for the join below).
                    self._native_store.unhost_locked(row)
                if lanes is not None:
                    self._promotions += 1
                    popped.append((row, lanes))
                    self._promoted_rows.add(row)  # idle-demotion candidate
                    self._promoted_at[row] = self.clock()
                    # Keep the lanes snapshot-visible until the device
                    # join lands (see _promoting's init comment).
                    self._promoting[row] = lanes
            self._promote_pending.clear()
        if not popped:
            return
        # One lane delta per nonzero lane (an own-lane carrier for an
        # elapsed-only row), joined through the engine's own merge tick in
        # slices of the feeder's drain budget: the tick's warmed shapes.
        cols: List[Tuple[int, int, int, int, int]] = []
        for row, lanes in popped:
            slots = np.flatnonzero(lanes.added | lanes.taken)
            if slots.size == 0 and not lanes.elapsed_ns:
                continue
            if slots.size == 0:
                slots = np.array([self.node_slot])
            cols += [
                (row, int(slot), int(lanes.added[slot]),
                 int(lanes.taken[slot]), lanes.elapsed_ns)
                for slot in slots
            ]
        step = MAX_MERGE_ROWS * self._commit_blocks
        for lo in range(0, len(cols), step):
            mat = np.array(cols[lo : lo + step], np.int64).T.copy()
            self._apply(DeltaArrays(*mat, np.zeros(mat.shape[1], bool)), ())
        # All chunk joins have landed: the staged lanes are now fully
        # represented in the device planes, so drop the snapshot aliases.
        # (pop, not clear — an eviction may have already dropped some.)
        with self._host_mu:
            for row, _lanes in popped:
                self._promoting.pop(row, None)

    def _host_absorb_ingest(
        self,
        rows: np.ndarray,
        slots: np.ndarray,
        added: np.ndarray,
        taken: np.ndarray,
        elapsed: np.ndarray,
        scalar,
    ) -> Optional[np.ndarray]:
        """Fold rx deltas addressed to host-resident rows into their host
        lanes (the same elementwise max-join the device computes — exact,
        and the own-lane single-writer rule holds because rx deltas only
        RAISE lanes, never take). Returns a keep-mask for the caller's
        chunk (False ⇒ absorbed here; unpin those rows), or None when
        nothing in the chunk is hosted.

        Why absorb instead of promote: a cold bucket in a cluster gets its
        own state echoed back within one RTT (state broadcast + incast
        reply, repo.go:86-90) — promoting on any rx would end every hosted
        bucket after its first take. Promotion still happens for (a)
        scalar-semantics (v1 reference peer) deltas, whose
        deficit-attribution kernel needs the device path, and (b) rx
        pressure past HOST_PROMOTE_TAKES per window — a remotely-hot
        bucket belongs on the device."""
        if not self._hosted:
            return None
        keep = np.ones(len(rows), dtype=bool)
        now = self.clock()
        with self._host_mu:
            # The residency mask is read UNDER the lock: an idle demotion
            # flips flags inside its _host_mu commit, so reading here means
            # either we see the flip (absorb host-side) or the demotion's
            # pin re-check sees our caller's pin (taken at assign, before
            # this call) and skips the row — no delta can slip device-ward
            # into a row that is about to be zeroed.
            mask = self._hosted_flag[rows]
            if not mask.any():
                return None
            for i in np.flatnonzero(mask):
                row = int(rows[i])
                lanes = self._hosted.get(row)
                if lanes is None:
                    continue  # promoted since the mask was taken: keep
                if scalar is not None and scalar[i]:
                    self._promote_locked(row)
                    continue  # delta rides the tick; the feeder joins the
                    # lanes (_drain_promotions) before applying it
                slot = int(slots[i])
                if lanes.added[slot] < added[i]:
                    lanes.added[slot] = added[i]
                if lanes.taken[slot] < taken[i]:
                    lanes.taken[slot] = taken[i]
                if lanes.elapsed_ns < elapsed[i]:
                    lanes.elapsed_ns = int(elapsed[i])
                keep[i] = False
                lanes.roll_window(now)
                lanes.win_rx += 1
                if lanes.win_rx > HOST_PROMOTE_TAKES:
                    self._promote_locked(row)
        return keep

    def _drop_hosted_rows(self, rows) -> None:
        """Forget host-resident state for rows leaving service (eviction /
        release): must run after unbind and before recycle, or a future
        re-bind of the row would inherit a dead bucket's lanes."""
        if not self._hosted and not self._promoted_rows:
            return
        with self._host_mu:
            for row in rows:
                # A recycled row must not stay an idle-demotion candidate.
                self._promoted_rows.discard(int(row))
                self._promoted_at.pop(int(row), None)
                if self._hosted_flag[row]:
                    self._hosted.pop(int(row), None)
                    self._hosted_flag[row] = False
                    if self._native_store is not None:
                        self._native_store.unhost_locked(int(row))
                # A stale pending entry would promote (and de-host) the
                # NEXT bucket bound to this recycled row after one take.
                self._promote_pending.discard(int(row))
                # A staged mid-promotion entry would resurrect the dead
                # bucket's lanes into a snapshot of the recycled row.
                self._promoting.pop(int(row), None)

    # True on the single-device engine; MeshEngine opts out (its state is
    # sharded — the per-row gather/zero pair is unmeasured there).
    _demotion_capable = True

    # Delta blocks one tick may drain and coalesce into a single commit
    # dispatch; MeshEngine opts down to 1 (its fused shard_map step has
    # its own per-block routing and no commit-ring kernel).
    _commit_blocks = COMMIT_BLOCKS
    # Adaptive commit-block sizing (PATROL_COMMIT_BLOCKS=auto): the
    # feeder re-sizes _commit_blocks per tick from backlog + measured
    # device-commit cost. MeshEngine pins it off (fused-step drains have
    # their own routing economics, unmeasured under auto).
    _commit_blocks_auto = COMMIT_BLOCKS_AUTO
    # Raw-plane device ingest (ops/ingest.py): MeshEngine opts out — a
    # decode_fold_raw dispatch against its sharded planes is unmeasured,
    # and the delta plane falls back to the python decode there.
    _raw_ingest_capable = True
    # Inline interval fold (ingest_interval's delta_fold dispatch on the
    # rx thread): MeshEngine opts out — against SHARDED planes the fold
    # is a collective program, and launching one from the rx context
    # both holds the state mutex across a mesh rendezvous and (on the
    # forced-host-device platform) can wedge the shared event loop.
    # Opt-outs route the interval through the queued classify path so
    # the lanes merge inside the feeder's own fused step.
    _interval_fold_capable = True

    def _maybe_demote(self, tickets, deltas) -> None:
        """Feeder-only: at demote-window rollover, return quiet promoted
        rows to host residency. Exact by construction — the row's device
        planes are gathered into fresh host lanes, THEN the device row is
        zeroed (flag→zero order, so the state is never in neither place;
        a snapshot in between max-joins identical values, which is
        idempotent).

        Safety against concurrent work, in order:
        * in-hand deltas (this tick's drain) would merge into the zeroed
          row — rows with deltas in hand are skipped;
        * any OTHER queued/in-flight work holds a directory pin, so a row
          is only eligible when its pin count exactly equals the pins of
          this tick's own drained tickets (which the re-route then serves
          host-side). The pin re-check runs under _host_mu: an ingest that
          classified the row device-ward before our flag flip necessarily
          pinned it first (assign→classify order), so it is visible here;
          one that classifies after sees the flag and absorbs host-side.
        * the whole gather→flag→zero runs under _evict_mu, so eviction /
          release can't unbind or recycle a row mid-demotion (same
          exclusion the promotion drain uses)."""
        if not (HOST_FASTPATH and self._demotion_capable):
            return
        if self._demotion_paused:
            return
        now = self.clock()
        if self._demote_win_start is None:
            self._demote_win_start = now
            return
        if now - self._demote_win_start <= HOST_DEMOTE_WINDOW_NS:
            return
        counts, self._dev_window = self._dev_window, {}
        self._demote_win_start = now
        with self._host_mu:
            cands = [
                r for r in self._promoted_rows
                if counts.get(r, 0) < HOST_DEMOTE_TAKES
                # Anchor eligibility to the ROW's promotion time, not the
                # global window: a row promoted mid-window (or right after
                # a long idle gap left the window stale) has only a
                # truncated count — demoting it one tick after a hot-burst
                # promotion would flap. It must have been device-resident
                # for at least one full window first.
                and now - self._promoted_at.get(r, now)
                >= HOST_DEMOTE_WINDOW_NS
            ]
        if not cands:
            return
        own_pins: Dict[int, int] = {}
        for t in tickets:
            own_pins[t.row] = own_pins.get(t.row, 0) + 1
        delta_rows = (
            set(int(r) for r in deltas.rows) if deltas is not None else set()
        )
        with self._evict_mu:
            elig = []
            for row in cands:
                if row in delta_rows:
                    continue
                if not self.directory._bound[row]:
                    self._promoted_rows.discard(row)
                    self._promoted_at.pop(row, None)
                    continue
                if int(self.directory.pins[row]) != own_pins.get(row, 0):
                    continue  # queued work beyond this tick pins the row
                elig.append(row)
            if not elig:
                return
            pn, el = self.read_rows(elig)  # ONE padded gather
            demoted: List[int] = []
            with self._host_mu:
                # Re-check the pause under the lock: checkpoint restore
                # sets it, then snapshots _hosted under this same lock —
                # so no demotion can commit after restore's snapshot.
                if self._demotion_paused:
                    return
                for i, row in enumerate(elig):
                    if int(self.directory.pins[row]) != own_pins.get(row, 0):
                        continue  # pinned since the outer check
                    if self._hosted_flag[row]:
                        continue
                    if self._native_store is not None:
                        lanes = self._native_store.host_locked(row)
                    else:
                        lanes = HostLanes(self.config.nodes)
                    lanes.added[:] = pn[i][:, 0]
                    lanes.taken[:] = pn[i][:, 1]
                    lanes.elapsed_ns = int(el[i])
                    lanes.win_start_ns = now
                    self._hosted[row] = lanes
                    self._hosted_flag[row] = True
                    self._promoted_rows.discard(row)
                    self._promoted_at.pop(row, None)
                    demoted.append(row)
            if demoted:
                k = _pad_size(len(demoted), lo=8, hi=1 << 20)
                rows_arr = np.full(k, demoted[0], np.int32)
                rows_arr[: len(demoted)] = demoted
                with self._state_mu:
                    self.state = zero_rows_jit(self.state, jnp.asarray(rows_arr))
                    self._state_gen += 1
                self._demotions += len(demoted)
                log.debug("demoted %d idle buckets to host residency", len(demoted))

    def flush_hosted(self, timeout: float = 10.0) -> int:
        """Promote every host-resident bucket to the device path (exact
        batched join). Used by checkpoint RESTORE, whose dense max-join
        only sees device planes. Returns rows promoted; raises
        ``TimeoutError`` if the feeder's join hasn't landed within
        ``timeout`` — a silent partial flush would let the caller proceed
        against planes that never received the host-lane join (restore
        would then max-join into still-hosted rows and drop spend).

        The drain itself runs on the FEEDER (we only mark + wait): a
        drain on this thread would flip residency flags, release the
        host lock, and only then take the state lock for the join — a
        racing take could route device-ward and be applied by the feeder
        against pre-join planes (over-admission, and the later max-join
        would erase the smaller own-lane debit). Feeder-driven drains
        flip and join strictly before the same tick's _apply, which is
        the ordering the promotion design relies on."""
        with self._host_mu:
            rows = list(self._hosted.keys())
            self._promote_pending.update(rows)
        if not rows:
            return 0
        if self._stopped:
            # Feeder is gone and no traffic can race a stopped engine:
            # drain inline.
            self._drain_promotions()
            return len(rows)
        with self._cond:
            self._cond.notify()
        deadline = time.monotonic() + timeout
        ours = set(rows)
        while time.monotonic() < deadline:
            with self._host_mu:
                # A row leaves _promote_pending at the drain's pop and
                # leaves _promoting only after the device join lands —
                # absence from both is the exact "flush visible in device
                # planes" signal. Scoped to OUR rows: on a live engine,
                # ongoing traffic keeps feeding new promotions, and a
                # global-emptiness wait could spin past the deadline (and
                # spuriously raise) with our join long landed.
                if not (ours & self._promote_pending) and not (
                    ours & self._promoting.keys()
                ):
                    return len(rows)
            time.sleep(0.0005)
        raise TimeoutError(
            f"flush_hosted: promotion join for {len(rows)} rows did not "
            f"land within {timeout}s"
        )

    # -- cert-kit kernel families (ops/gcra.py, ops/concurrency.py, ----
    # ops/hierquota.py): synchronous microbatch entry points, one device
    # dispatch per call against the SHARED planes — these families ride
    # the same replication/merge path as the bucket take, so they share
    # its state lock and donate-and-replace discipline. Certified by
    # check.sh stage 9 (patrol-cert); registered in PROVE_ROOTS.

    def gcra_take(
        self, rows, now_ns, emission_ns, tol_ns, nreq
    ):
        """GCRA conformance microbatch → GcraResult (device arrays)."""
        req = GcraRequest(
            rows=jnp.asarray(np.asarray(rows, np.int32)),
            now_ns=jnp.asarray(np.asarray(now_ns, np.int64)),
            emission_ns=jnp.asarray(np.asarray(emission_ns, np.int64)),
            tol_ns=jnp.asarray(np.asarray(tol_ns, np.int64)),
            nreq=jnp.asarray(np.asarray(nreq, np.int64)),
        )
        with self._state_mu:
            self.state, res = gcra_take_batch_jit(
                self.state, req, self.node_slot
            )
            self._state_gen += 1
        return res

    def conc_acquire(
        self, rows, limit_nt, count_nt, nreq, releases
    ):
        """Concurrency acquire/release microbatch → ConcResult."""
        req = ConcRequest(
            rows=jnp.asarray(np.asarray(rows, np.int32)),
            limit_nt=jnp.asarray(np.asarray(limit_nt, np.int64)),
            count_nt=jnp.asarray(np.asarray(count_nt, np.int64)),
            nreq=jnp.asarray(np.asarray(nreq, np.int64)),
            releases=jnp.asarray(np.asarray(releases, np.int64)),
        )
        with self._state_mu:
            self.state, res = conc_acquire_batch_jit(
                self.state, req, self.node_slot
            )
            self._state_gen += 1
        return res

    def quota_take(
        self,
        rows_global,
        rows_tenant,
        rows_user,
        limit_global_nt,
        limit_tenant_nt,
        limit_user_nt,
        count_nt,
        nreq,
    ):
        """Hierarchical-quota path-take microbatch → QuotaResult."""
        req = QuotaRequest(
            rows_global=jnp.asarray(np.asarray(rows_global, np.int32)),
            rows_tenant=jnp.asarray(np.asarray(rows_tenant, np.int32)),
            rows_user=jnp.asarray(np.asarray(rows_user, np.int32)),
            limit_global_nt=jnp.asarray(np.asarray(limit_global_nt, np.int64)),
            limit_tenant_nt=jnp.asarray(np.asarray(limit_tenant_nt, np.int64)),
            limit_user_nt=jnp.asarray(np.asarray(limit_user_nt, np.int64)),
            count_nt=jnp.asarray(np.asarray(count_nt, np.int64)),
            nreq=jnp.asarray(np.asarray(nreq, np.int64)),
        )
        with self._state_mu:
            self.state, res = quota_take_batch_jit(
                self.state, req, self.node_slot
            )
            self._state_gen += 1
        return res

    def snapshot_planes(self) -> Tuple[np.ndarray, np.ndarray]:
        """Host copies of the device planes with every host-resident
        bucket's lanes max-joined in — the checkpoint-save view. Atomic
        against promotions: copy and join run under ``_host_mu``, and a
        bucket mid-promotion lives in exactly one of three places we all
        read — ``_hosted`` (not popped yet), ``_promoting`` (popped, device
        join in flight), or the device planes (join landed; the staged
        entry may linger until the drain's clear, which is harmless — the
        join is a max, so joining it twice is exact). The drain never
        holds both locks across its pop→merge window; ``_promoting`` is
        what makes this read atomic anyway. Residency is untouched — a
        save must not demote every cold bucket it snapshots. Host serving
        stalls for the copy; checkpoint cadence is operator-controlled and
        rare."""
        with self._host_mu:
            with self._state_mu:
                pn = np.array(self.state.pn)
                elapsed = np.array(self.state.elapsed)
            for row, lanes in itertools.chain(
                self._hosted.items(), self._promoting.items()
            ):
                np.maximum(pn[row, :, 0], lanes.added, out=pn[row, :, 0])
                np.maximum(pn[row, :, 1], lanes.taken, out=pn[row, :, 1])
                if elapsed[row] < lanes.elapsed_ns:
                    elapsed[row] = lanes.elapsed_ns
        return pn, elapsed

    def drain_native_promotions(self) -> None:
        """Promotions-only drain of the native store: no broadcast
        building, no dirty-row pops. The front's pump calls this when a
        poll wake finds the store's promotion-event counter moved but the
        broadcast cadence gate is still closed, so a take-pressure-hot
        bucket joins the device path promptly instead of waiting out
        ``max(poll tick, 4x last drain cost)`` (ADVICE r5). Dirty rows
        keep their queue entries and flags for the cadence-gated drain."""
        st = self._native_store
        if st is None:
            return
        with self._host_mu:
            for row in st.drain_promotes_locked():
                if row in self._hosted:
                    self._promote_locked(row)

    def drain_native_broadcasts(self) -> None:
        """Turn the C++ front's coalesced take effects into replication:
        emit each dirty row's LATEST full state once (CvRDT: a later state
        subsumes all earlier ones — lossless coalescing of the reference's
        per-take broadcast, repo.go:123-127) and mark take-pressure
        promotions. Called by the native front's pump each cycle; the C++
        side wakes the pump promptly via the poll predicate."""
        st = self._native_store
        if st is None:
            return
        # The C++ front serves takes without entering Python at all —
        # the pump's drain cycle is the one periodic seam that can keep
        # the GC cadence alive under pure in-front load.
        self._kick_gc_if_due(self.clock())
        if self.on_broadcast is None:
            # Standalone node: drain the queues (promotion marks still
            # matter; dirty flags must clear) without building states.
            with self._host_mu:
                while True:
                    dirty, _snap, promotes = st.drain_locked()
                    for row in promotes:
                        if row in self._hosted:
                            self._promote_locked(row)
                    if not dirty and not promotes:
                        return
        n = self.config.nodes
        while True:
            # The lock-held work is MINIMAL: the C++ drain copies each
            # dirty row's lanes into a flat buffer inside the call, and
            # Python only captures (membership, name, cap) per row —
            # _host_mu is the very mutex the epoll thread's in-front
            # takes block on, and a per-row Python pass under it (the
            # first r5 shape) held it for ~ms per drain at ~1k dirty
            # rows, surfacing as the front's p99 tail. Wire construction
            # runs OUTSIDE against the copies. Loop until both queues
            # drain: the C++ side pops a buffer's worth per call.
            meta: List[Tuple[int, str, int]] = []  # (snap idx, name, cap)
            with self._host_mu:
                dirty, lanes_snap, promotes = st.drain_locked()
                for row in promotes:
                    if row in self._hosted:
                        self._promote_locked(row)
                for i, row in enumerate(dirty):
                    if not self._hosted_flag[row]:
                        continue  # promoted/evicted since marked: its
                        # state rides the device completion broadcasts
                    name = self.directory.name_of(row)
                    if name is None:
                        continue
                    meta.append((i, name, int(self.directory.cap_base_nt[row])))
            states: List[wire.WireState] = []
            for i, name, cap in meta:
                row_snap = lanes_snap[i]
                own_a = int(row_snap[self.node_slot])
                own_t = int(row_snap[n + self.node_slot])
                elapsed = int(row_snap[2 * n])
                if not (own_a or own_t or elapsed or cap):
                    continue  # zero state is the incast marker
                states.append(
                    wire.from_nanotokens(
                        name, cap + int(row_snap[:n].sum()),
                        int(row_snap[n : 2 * n].sum()), elapsed,
                        origin_slot=self.node_slot, cap_nt=cap,
                        lane_added_nt=own_a, lane_taken_nt=own_t,
                    )
                )
            if states:
                self._emit_broadcasts(states)
            if not dirty and not promotes:
                return

    def take(
        self, name: str, rate: Rate, count: int, now_ns: Optional[int] = None
    ) -> Tuple[int, bool, bool]:
        """Blocking take: returns (remaining, ok, created)."""
        ticket, created = self.submit_take(name, rate, count, now_ns)
        ticket.wait()
        return ticket.remaining, ticket.ok, created

    def submit_takes_batch(
        self,
        names: Sequence[str],
        rates: Sequence[Rate],
        counts: Sequence[int],
        now_ns: Optional[int] = None,
        batch: int = 0,
        answer: Optional[tuple] = None,
    ) -> Optional[List[Tuple[TakeTicket, bool]]]:
        """Batched :meth:`submit_take` for the native HTTP pump: ONE
        directory pass (assign_many), one capacity init, one queue
        append + wake-up, instead of per-request lock/notify churn.
        Returns [(ticket, created), ...] in request order, or None when
        the pool is spent with every row pinned (the caller falls back or
        fails the batch; no ticket was completed). Under the memory
        budget's hard watermark, requests for NEW names come back as
        already-completed shed tickets (ok=False) — per-request 429s,
        never a failed batch. ``answer`` = (sink, tags, streams), one
        tag and stream per request: each ticket carries its answer
        address from creation, so whichever thread completes it hands
        the answer back (:func:`hand_back`); the tickets this call
        completes itself (host-served, shed) are the caller's to hand
        back once it returns."""
        now = self.clock() if now_ns is None else now_ns
        if self._max_buckets or self._bytes_budget:
            unknown = [
                i for i, n in enumerate(names)
                if self.directory.lookup(n) is None
            ]
            if unknown and self._shed_new_names(now, len(unknown)):
                shed = set(unknown)
                out: List = [None] * len(names)
                keep = [i for i in range(len(names)) if i not in shed]
                if keep:
                    sub = self._submit_takes_batch_inner(
                        [names[i] for i in keep],
                        [rates[i] for i in keep],
                        [counts[i] for i in keep],
                        now,
                        batch,
                        answer and (
                            answer[0],
                            [answer[1][i] for i in keep],
                            [answer[2][i] for i in keep],
                        ),
                    )
                    if sub is None:
                        return None
                    for i, r in zip(keep, sub):
                        out[i] = r
                for i in unknown:
                    t = _addressed(
                        TakeTicket(names[i], 0, rates[i], int(counts[i]), now, batch),
                        answer, i,
                    )
                    t.shed = True  # overload shed, not a rate deny
                    t.complete(0, False)  # never pinned, never queued
                    out[i] = (t, False)
                return out
        return self._submit_takes_batch_inner(
            list(names), list(rates), list(counts), now, batch, answer
        )

    def _submit_takes_batch_inner(
        self,
        names: Sequence[str],
        rates: Sequence[Rate],
        counts: Sequence[int],
        now: int,
        batch: int = 0,
        answer: Optional[tuple] = None,
    ) -> Optional[List[Tuple[TakeTicket, bool]]]:
        res = self._assign_many_pinned(list(names), now, with_fresh=True)
        if res is None:
            return None
        rows, bind_fresh = res
        created_arr = self.directory.cap_base_nt[rows] == 0
        # Sequential-parity: only the FIRST occurrence of a row in the
        # batch counts as the creating miss (submit_take called twice
        # returns created=(True, False)).
        first = np.zeros(len(rows), dtype=bool)
        first[np.unique(rows, return_index=True)[1]] = True
        created = (created_arr & first).tolist()
        self.directory.init_cap_base_many(
            rows, np.asarray([r.freq for r in rates], np.int64) * NANO
        )
        self.directory.note_rate_many(
            rows, np.asarray([r.per_ns for r in rates], np.int64)
        )
        # Tombstone re-seeds for rows bound fresh by this batch (one per
        # first occurrence): applied into the fresh host lanes below, or
        # queued into the tick's merge phase for the device path.
        fresh_first_all = bind_fresh & first
        seeds: Dict[int, Tuple[int, int, int]] = {}
        if fresh_first_all.any() and self.directory.has_tombstones():
            for i in np.flatnonzero(fresh_first_all):
                s = self._pop_tombstone_seed(names[i], int(rows[i]))
                if s is not None:
                    seeds[int(rows[i])] = s
        # Host fast path: serve host-resident (and fresh) rows in-process,
        # in batch order; only the device-resident remainder rides a tick.
        # The flag is re-read per request (not precomputed): a fresh row
        # hosted by its first occurrence must catch the row's LATER
        # occurrences in this same batch, or they would run against the
        # row's empty device state. Residency eligibility is the
        # DIRECTORY's bind-fresh signal — a cap==0 proxy would mis-host
        # rows that already carry replicated device lanes (cap-less raw
        # lane deltas never set the cap).
        tickets = [
            _addressed(
                TakeTicket(
                    names[i], int(rows[i]), rates[i], int(counts[i]), now, batch
                ),
                answer, i,
            )
            for i in range(len(names))
        ]
        host_served: Set[int] = set()
        if HOST_FASTPATH:
            fresh_first = fresh_first_all
            # Candidates only — the device-only common case stays one
            # vectorized probe. Every later occurrence of a row hosted by
            # its own first occurrence has bind_fresh True, so it is in
            # the candidate set and its live flag re-read routes it host;
            # rows hosted by a CONCURRENT thread mid-batch are caught by
            # the tick's residency re-route, like submit_take.
            bc: List[wire.WireState] = []
            for i in np.flatnonzero(self._hosted_flag[rows] | bind_fresh):
                if self._hosted_flag[rows[i]] or fresh_first[i]:
                    if self._host_serve_ticket(
                        tickets[i], bool(fresh_first[i]), bc,
                        seeds.get(int(rows[i])),
                    ):
                        host_served.add(int(i))
                        if fresh_first[i]:
                            # Seed landed in the fresh host lanes.
                            seeds.pop(int(rows[i]), None)
            self._emit_broadcasts(bc)
        queued = [t for i, t in enumerate(tickets) if i not in host_served]
        if host_served and not queued:
            # Fully host-served batch: no feeder work queued — kick the
            # GC cadence like the scalar fast path does.
            self._kick_gc_if_due(now)
        if queued or seeds:
            with self._cond:
                for srow, s in seeds.items():
                    # Un-hosted fresh binds: the seed rides the same
                    # tick's merge phase, ahead of the queued takes.
                    self._deltas.append(_Delta(srow, self.node_slot, *s))
                for t in queued:
                    self._enqueue_take_locked(t)
                self._cond.notify()
        return list(zip(tickets, created))

    def ingest_delta(self, state: wire.WireState, slot: int, scalar: bool = False) -> bool:
        """Queue one replication delta for merge; returns created flag.
        Dropped (not an error) if the pool is spent with everything pinned —
        replication is loss-tolerant by CRDT design (README.md:41-43).

        Wire semantics (the mixed-cluster interop contract; see ops/wire.py):

        * lane trailer present — a patrol_tpu peer's exact PN lane values:
          merge them directly (the float header is its aggregate view, for
          reference peers only); adopt ``cap_nt`` as this row's cap_base
          when still unset.
        * ``cap_nt`` only (with-cap trailer) — the header is the sender's
          capacity-included AGGREGATE but the exact lane is absent: subtract
          the wire cap and route through the deficit-attribution kernel
          (attributing the aggregate to the sender's lane directly would
          double-count every other lane's echoed grants).
        * ``scalar=True`` (v1 packet, no trailer) — a reference peer's
          scalar-max aggregates: subtract OUR cap_base and route through
          the deficit-attribution kernel. Unknowable before the first local
          take reveals the capacity ⇒ dropped until then (the reference
          rebroadcasts full state on every take, so nothing is lost).
        * none of the above — the header carries raw own-lane values: a
          base-trailer peer (grants-only lane header) or an internal
          raw-lane join (upsert seam). Plain lane max-merge.
        """
        now = self.clock()
        if not 0 <= slot < self.config.nodes:
            log.warning("delta slot %d out of range, dropped", slot)
            return False
        try:
            row, created = self._assign_pinned(state.name, now)
        except DirectoryFullError:
            log.warning("pool spent (all pinned); delta for %r dropped", state.name)
            return False
        if created and self.directory.has_tombstones():
            seed = self._pop_tombstone_seed(state.name, row)
            if seed is not None:
                with self._cond:
                    self._deltas.append(_Delta(row, self.node_slot, *seed))
                    self._cond.notify()
        # patrol-audit staleness stamp (remote absorb; racy by design).
        self.directory.last_remote_ns[row] = now
        added_nt = state.added_nt
        taken_nt = state.taken_nt
        if state.cap_nt is not None:
            if state.cap_nt > 0:
                self.directory.init_cap_base(row, state.cap_nt)
            if state.lane_added_nt is not None and state.lane_taken_nt is not None:
                added_nt = state.lane_added_nt
                taken_nt = state.lane_taken_nt
                scalar = False
            else:
                added_nt = max(added_nt - state.cap_nt, 0)
                scalar = True
        elif scalar:
            base = int(self.directory.cap_base_nt[row])
            if base == 0:
                # Capacity unknown on this row: can't separate the reference
                # peer's lazy-init cap from its grants yet. Drop; its next
                # full-state broadcast (every take) re-delivers.
                self.directory.unpin_rows([row])
                self._scalar_dropped += 1
                return created
            added_nt = max(added_nt - base, 0)
        if HOST_FASTPATH and self._hosted_flag[row]:
            # Scalar-fold twin of _host_absorb_ingest for the per-packet
            # path: same join, zero array allocations.
            absorbed = False
            with self._host_mu:
                lanes = self._hosted.get(row)
                if lanes is not None:
                    if scalar:
                        self._promote_locked(row)  # delta rides the tick
                    else:
                        if lanes.added[slot] < added_nt:
                            lanes.added[slot] = added_nt
                        if lanes.taken[slot] < taken_nt:
                            lanes.taken[slot] = taken_nt
                        if lanes.elapsed_ns < state.elapsed_ns:
                            lanes.elapsed_ns = state.elapsed_ns
                        lanes.roll_window(now)
                        lanes.win_rx += 1
                        if lanes.win_rx > HOST_PROMOTE_TAKES:
                            self._promote_locked(row)
                        absorbed = True
            if absorbed:
                self.directory.unpin_rows([row])
                if state.trace_id:
                    # Host-absorbed remote delta: the merge span completes
                    # here, joined to the sender's take span by the id.
                    trace_mod.SPANS.add(
                        state.trace_id, self.node_slot, "merge", state.name,
                        time.perf_counter_ns(), 0,
                    )
                return created
        delta = _Delta(row, slot, added_nt, taken_nt, state.elapsed_ns, scalar)
        if state.trace_id:
            delta.trace_id = state.trace_id
            delta.trace_name = state.name
        with self._cond:
            self._deltas.append(delta)
            self._cond.notify()
        return created

    def ingest_deltas_batch(
        self,
        names: Sequence[str],
        slots: Sequence[int],
        added_nt: Sequence[int],
        taken_nt: Sequence[int],
        elapsed_ns: Sequence[int],
        caps_nt: Optional[Sequence[int]] = None,
        lane_added_nt: Optional[Sequence[int]] = None,
        lane_taken_nt: Optional[Sequence[int]] = None,
        scalar: Optional[Sequence[bool]] = None,
    ) -> int:
        """Bulk ingest from the native receive path: one vectorized
        directory pass, one queue append, one wake-up — the feeder loop the
        Go reference runs one packet per iteration (repo.go:54-92).
        Returns deltas accepted (the whole batch is dropped only when the
        pool is spent with every row pinned).

        Per-delta wire semantics (−1 = field absent; see ingest_delta):
        lane values ≥0 ⇒ exact PN lane merge; cap ≥0 only ⇒ header minus
        wire cap, deficit-attribution merge; neither ⇒ ``scalar[i]`` picks
        between v1 scalar state (no trailer: deficit-attribution merge
        against OUR cap_base, dropped while that capacity is unknown) and a
        base-trailer peer's raw own-lane header (plain lane merge;
        the default when ``scalar`` is omitted, matching prior-version
        senders). ``caps_nt=None`` entirely ⇒ raw lane values (internal
        feeders: bench replay)."""
        now = self.clock()
        slots_a = np.asarray(slots, dtype=np.int64)
        keep = (slots_a >= 0) & (slots_a < self.config.nodes)
        caps_a = None if caps_nt is None else np.asarray(caps_nt, dtype=np.int64)
        lane_a = None if lane_added_nt is None else np.asarray(lane_added_nt, np.int64)
        lane_t = None if lane_taken_nt is None else np.asarray(lane_taken_nt, np.int64)
        scalar_a = None if scalar is None else np.asarray(scalar, dtype=bool)
        if caps_a is None and scalar_a is not None:
            # Honor the scalar flags even without a caps array (parity with
            # ingest_delta(..., scalar=True)): all caps absent.
            caps_a = np.full(len(slots_a), -1, dtype=np.int64)
        if not keep.all():
            idx = np.flatnonzero(keep)
            names = [names[i] for i in idx]
            slots_a = slots_a[idx]
            added_nt = np.asarray(added_nt, dtype=np.int64)[idx]
            taken_nt = np.asarray(taken_nt, dtype=np.int64)[idx]
            elapsed_ns = np.asarray(elapsed_ns, dtype=np.int64)[idx]
            if caps_a is not None:
                caps_a = caps_a[idx]
            if lane_a is not None:
                lane_a, lane_t = lane_a[idx], lane_t[idx]
            if scalar_a is not None:
                scalar_a = scalar_a[idx]
        if not len(names):
            return 0
        accepted = 0
        # Split oversize batches so one chunk never exceeds a tick's budget.
        for lo in range(0, len(names), MAX_MERGE_ROWS):
            hi = lo + MAX_MERGE_ROWS
            chunk_names = names[lo:hi]
            res = self._assign_many_pinned(chunk_names, now, with_fresh=True)
            if res is None:
                log.warning(
                    "pool spent (all pinned); %d deltas dropped", len(chunk_names)
                )
                continue
            rows, fresh = res
            if fresh.any():
                self._reseed_fresh_rows(chunk_names, rows, fresh)
            accepted += self._classify_queue_chunk(
                rows,
                slots_a[lo:hi],
                np.asarray(added_nt[lo:hi], dtype=np.int64),
                np.asarray(taken_nt[lo:hi], dtype=np.int64),
                np.asarray(elapsed_ns[lo:hi], dtype=np.int64),
                None if caps_a is None else caps_a[lo:hi],
                None if lane_a is None else lane_a[lo:hi],
                None if lane_t is None else lane_t[lo:hi],
                None if scalar_a is None else scalar_a[lo:hi],
            )
        return accepted

    def ingest_interval(
        self,
        names: Sequence[str],
        slots: Sequence[int],
        caps_nt: Sequence[int],
        added_nt: Sequence[int],
        taken_nt: Sequence[int],
        elapsed_ns: Sequence[int],
    ) -> int:
        """Bulk ingest of ONE decoded delta-interval datagram (wire v2,
        net/delta.py): exact absolute PN-lane values only — the delta
        plane never ships scalar aggregates, so there is no deficit
        attribution and no capacity gating here. One vectorized directory
        pass, host-lane absorption for host-resident rows (same join as
        the classic rx path), then a SINGLE sentinel-padded scatter-max
        dispatch (ops/delta.delta_fold) for the device remainder — a
        whole interval lands as one batched plane commit instead of
        hundreds of queued per-delta objects. Returns deltas accepted;
        drops are loss-tolerant by CRDT design, like every ingest path."""
        if not self._interval_fold_capable:
            # Sharded planes (_interval_fold_capable=False): the entries
            # are exact PN lane values with caps, which is precisely the
            # lane-trailer case of the classify path — queue them for the
            # feeder's fused step instead of folding here on rx.
            return self.ingest_deltas_batch(
                names,
                slots,
                added_nt,
                taken_nt,
                elapsed_ns,
                caps_nt=caps_nt,
                lane_added_nt=added_nt,
                lane_taken_nt=taken_nt,
            )
        now = self.clock()
        slots_a = np.asarray(slots, dtype=np.int64)
        keep = (slots_a >= 0) & (slots_a < self.config.nodes)
        caps_a = np.asarray(caps_nt, dtype=np.int64)
        added_a = np.asarray(added_nt, dtype=np.int64)
        taken_a = np.asarray(taken_nt, dtype=np.int64)
        elapsed_a = np.asarray(elapsed_ns, dtype=np.int64)
        if not keep.all():
            idx = np.flatnonzero(keep)
            names = [names[i] for i in idx]
            slots_a, caps_a = slots_a[idx], caps_a[idx]
            added_a, taken_a, elapsed_a = added_a[idx], taken_a[idx], elapsed_a[idx]
        if not len(names):
            return 0
        accepted = 0
        for lo in range(0, len(names), MAX_MERGE_ROWS):
            hi = lo + MAX_MERGE_ROWS
            chunk_names = names[lo:hi]
            res = self._assign_many_pinned(chunk_names, now, with_fresh=True)
            if res is None:
                log.warning(
                    "pool spent (all pinned); %d interval deltas dropped",
                    len(chunk_names),
                )
                continue
            rows, fresh_c = res
            # patrol-audit staleness stamp: these rows just absorbed
            # remote-lane state (racy int64 write, sampler-only reader).
            self.directory.last_remote_ns[rows] = now
            if fresh_c.any():
                self._reseed_fresh_rows(chunk_names, rows, fresh_c)
            slots_c = slots_a[lo:hi]
            caps_c = np.maximum(caps_a[lo:hi], 0)
            added_c = np.maximum(added_a[lo:hi], 0)
            taken_c = np.maximum(taken_a[lo:hi], 0)
            elapsed_c = np.maximum(elapsed_a[lo:hi], 0)
            pos = caps_c > 0
            if pos.any():
                self.directory.init_cap_base_many(rows[pos], caps_c[pos])
            live = np.ones(len(rows), dtype=bool)
            if HOST_FASTPATH:
                keep_h = self._host_absorb_ingest(
                    rows, slots_c, added_c, taken_c, elapsed_c, None
                )
                if keep_h is not None:
                    absorbed = ~keep_h
                    if absorbed.any():
                        self.directory.unpin_rows(rows[absorbed])
                        accepted += int(absorbed.sum())
                        live = keep_h
            n = int(live.sum())
            if n == 0:
                continue
            k = _pad_size(n)
            rows_p = np.full(k, merge_mod.FOLD_PAD_ROW, np.int32)
            slots_p = np.zeros(k, np.int32)
            added_p = np.zeros(k, np.int64)
            taken_p = np.zeros(k, np.int64)
            elapsed_p = np.zeros(k, np.int64)
            rows_p[:n] = rows[live]
            slots_p[:n] = slots_c[live]
            added_p[:n] = added_c[live]
            taken_p[:n] = taken_c[live]
            elapsed_p[:n] = elapsed_c[live]
            batch = delta_ops.DeltaBatch(
                rows=jnp.asarray(rows_p),
                slots=jnp.asarray(slots_p),
                added_nt=jnp.asarray(added_p),
                taken_nt=jnp.asarray(taken_p),
                elapsed_ns=jnp.asarray(elapsed_p),
            )
            t0 = time.perf_counter_ns()
            with self._state_mu, _dispatch("delta_fold"):
                self.state = delta_ops.delta_fold_jit(self.state, batch)
            self._observe_device_commit("delta_fold", t0, n)
            self._ticks += 1
            self.directory.unpin_rows(rows[live])
            accepted += n
        return accepted

    def ingest_raw_planes(
        self,
        planes: np.ndarray,
        lengths: np.ndarray,
        walk=None,
        release: Optional[Callable[[], None]] = None,
    ) -> int:
        """Device-resident ingest (ops/ingest.py; ROADMAP item 1): raw
        dv2 datagram byte planes → joined state in ONE decode+fold
        dispatch. The wire→state path ships BYTES — framing walk, entry
        extraction, checksum/validation verdicts, sentinel-padding of
        invalid packets, and the scatter-max fold all run inside the
        kernel; the host contributes only what a device cannot: the
        directory pass resolving entry names to rows (vectorized, via
        the walk's name offsets/hashes — Python strings materialize only
        for first-seen buckets) and the host-lane split, which absorbs
        the kernel's ``hosted_mask`` output through the existing
        host-lane join.

        ``planes`` is uint8[P, ROW] (rows straight out of the rx ring —
        non-dv2 rows simply fail the in-kernel verdict via a zeroed
        length); ``walk`` is the caller's :func:`ops.ingest.walk`
        result when it already ran one (the delta plane's ack
        bookkeeping shares it); ``release`` is invoked on the completion
        pipeline once the shipped planes operand is READY (the
        StagingPool contract: device_put copies, so readiness means the
        ring plane is refillable) — or inline if the dispatch never
        happens. Returns deltas accepted (folded + host-absorbed)."""
        from patrol_tpu.ops import ingest as ingest_ops

        released = release is None

        def _release_inline() -> None:
            nonlocal released
            if not released:
                released = True
                release()

        try:
            planes = np.asarray(planes)
            lengths = np.ascontiguousarray(lengths, np.int32)
            if walk is None:
                walk = ingest_ops.walk(planes, lengths)
            if not walk.ok.any():
                # Nothing dispatch-worthy: every row failed the framing
                # walk, so the kernel would sentinel-pad the whole batch
                # and fold nothing. Skip the dispatch (a garbage flood
                # must not burn device programs) — the finally releases
                # the planes inline, honoring the ring contract.
                return 0
            P, row_w = planes.shape
            E = walk.name_len.shape[1]
            now = self.clock()
            live = walk.ok[:, None] & (
                np.arange(E)[None, :] < walk.count[:, None]
            )
            pi, ei = np.nonzero(live)
            rows_pe = np.full((P, E), _FOLD_PAD_ROW, np.int32)
            hosted_pe = np.zeros((P, E), dtype=bool)
            accepted = 0
            pinned: Optional[np.ndarray] = None
            keep_chunk_rows: Optional[np.ndarray] = None
            if pi.size:
                # Entry filter the python rx path applies per entry:
                # out-of-range slots and control-channel names never
                # reach the directory (nor the fold — their rows stay
                # sentinels).
                slots_f = walk.slot[pi, ei]
                off_f = walk.name_off[pi, ei].astype(np.int64)
                len_f = walk.name_len[pi, ei].astype(np.int32)
                first = planes[pi, np.clip(off_f, 0, row_w - 1)]
                ctrl = (len_f > 0) & (first == 0)
                keep = (slots_f >= 0) & (slots_f < self.config.nodes) & ~ctrl
                pi, ei = pi[keep], ei[keep]
                off_f, len_f = off_f[keep], len_f[keep]
            if pi.size:
                # The existing directory pass, raw form: vectorized
                # hashed lookup (pins hits), misses bound once per
                # bucket lifetime with tombstone re-seed.
                hashes_f = walk.name_hash[pi, ei]
                name_buf = ingest_ops.gather_name_rows(
                    planes, pi, off_f, len_f
                )
                rows_f = self.directory.lookup_hashed_pinned(
                    hashes_f, name_buf, len_f, now
                )
                miss = np.flatnonzero(rows_f < 0)
                for lo in range(0, miss.size, MAX_MERGE_ROWS):
                    mi = miss[lo : lo + MAX_MERGE_ROWS]
                    got = self._bind_wire_misses_pinned(
                        name_buf, len_f, hashes_f, mi, now
                    )
                    if got is not None:
                        rows_f[mi] = got
                bound = rows_f >= 0
                if bound.any():
                    b_rows = rows_f[bound].astype(np.int64)
                    pinned = b_rows
                    # patrol-audit staleness stamp (remote absorb; racy
                    # by design, sampler-only reader).
                    self.directory.last_remote_ns[b_rows] = now
                    caps_b = np.maximum(walk.cap[pi, ei][bound], 0)
                    pos = caps_b > 0
                    if pos.any():
                        self.directory.init_cap_base_many(
                            b_rows[pos], caps_b[pos]
                        )
                    if HOST_FASTPATH and self._hosted:
                        hosted_b = self._hosted_flag[b_rows]
                    else:
                        hosted_b = np.zeros(len(b_rows), dtype=bool)
                    rows_pe[pi[bound], ei[bound]] = b_rows
                    hosted_pe[pi[bound], ei[bound]] = hosted_b

            # ONE dispatch for the whole batch. The planes ship as-is
            # (rx-ring rows, no intermediate numpy repack); entry_off is
            # the walk's framing proposal the kernel RE-VALIDATES,
            # rows/hosted are the host plan; everything else — framing
            # chain, checksums, verdicts, sentinel padding, fold —
            # happens in-kernel.
            entry_off = np.maximum(walk.name_off - 1, 0)
            t0 = time.perf_counter_ns()
            planes_dev = jax.device_put(np.ascontiguousarray(planes))
            _obs_stage(hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT, int(pi.size))
            t0 = time.perf_counter_ns()
            with self._state_mu, _dispatch("decode_fold_raw"):
                (
                    self.state, _ok_d, _entry_ok, hosted_mask,
                    d_slot, _d_cap, d_added, d_taken, d_elapsed,
                ) = ingest_ops.decode_fold_raw_jit(
                    self.state, planes_dev, jnp.asarray(lengths),
                    jnp.asarray(entry_off), jnp.asarray(rows_pe),
                    jnp.asarray(hosted_pe),
                )
            _obs_stage(
                hist.STAGE_DISPATCH, t0, trace_mod.EV_COMMIT_DISPATCH,
                int(pi.size),
            )
            self._observe_device_commit("decode_fold_raw", t0, max(int(pi.size), 1))
            self._ticks += 1
            profiling.COUNTERS.inc("ingest_raw_device_dispatches")
            profiling.COUNTERS.inc(
                "ingest_raw_bytes_on_device",
                int(lengths[walk.ok].sum()) if walk.ok.any() else 0,
            )
            if release is not None:
                released = True

                def _commit_plane() -> None:
                    # Plane-recycle gate ON the completion pipeline: the
                    # rx buffer may not be reused before the kernel has
                    # consumed it. Runs on the completer, not the rx path.
                    jax.block_until_ready(planes_dev)  # patrol-lint: disable=PTD003
                    release()

                self._enqueue_completion(_commit_plane, (), {})

            folded = int(((rows_pe != _FOLD_PAD_ROW) & ~hosted_pe).sum())
            accepted += folded
            if hosted_pe.any():
                # Host-lane split, driven by the KERNEL's hosted-mask
                # output (valid ∩ hosted) and decoded entry values: the
                # readback joins them into the host lanes; entries whose
                # row promoted mid-flight ride the feeder tick instead.
                # Kernel-verdict readback: one batched D2H per rx ring,
                # only on the (rare) host-resident branch — the price of
                # letting the kernel, not the host, decide residency.
                hm = np.asarray(hosted_mask)  # patrol-lint: disable=PTD003
                hpi, hei = np.nonzero(hm)
                if hpi.size:
                    h_rows = rows_pe[hpi, hei].astype(np.int64)
                    h_slots = np.asarray(d_slot)[hpi, hei]  # patrol-lint: disable=PTD003
                    h_added = np.asarray(d_added)[hpi, hei]  # patrol-lint: disable=PTD003
                    h_taken = np.asarray(d_taken)[hpi, hei]  # patrol-lint: disable=PTD003
                    h_elapsed = np.maximum(
                        np.asarray(d_elapsed)[hpi, hei], 0  # patrol-lint: disable=PTD003
                    )
                    keep_h = self._host_absorb_ingest(
                        h_rows, h_slots, h_added, h_taken, h_elapsed, None
                    )
                    if keep_h is None:
                        keep_h = np.ones(len(h_rows), dtype=bool)
                    accepted += int((~keep_h).sum())
                    if keep_h.any():
                        keep_chunk_rows = h_rows[keep_h]
                        chunk = _DeltaChunk(
                            keep_chunk_rows, h_slots[keep_h],
                            h_added[keep_h], h_taken[keep_h],
                            h_elapsed[keep_h],
                        )
                        with self._cond:
                            self._deltas.append(chunk)
                            self._cond.notify()
                        accepted += chunk.n
            # Release this call's pins — except rows re-queued as a
            # feeder chunk, whose pins the tick's finally releases.
            if pinned is not None:
                if keep_chunk_rows is not None and keep_chunk_rows.size:
                    unpin = pinned.copy()
                    # One pin per entry was taken; the chunk keeps one
                    # per re-queued entry.
                    drop = np.zeros(len(unpin), dtype=bool)
                    remaining = {}
                    for r in keep_chunk_rows:
                        remaining[int(r)] = remaining.get(int(r), 0) + 1
                    for i, r in enumerate(unpin):
                        c = remaining.get(int(r), 0)
                        if c:
                            remaining[int(r)] = c - 1
                            drop[i] = True
                    self.directory.unpin_rows(unpin[~drop])
                else:
                    self.directory.unpin_rows(pinned)
            return accepted
        finally:
            _release_inline()

    def _classify_queue_chunk(
        self,
        rows: np.ndarray,
        slots_c: np.ndarray,
        added_c: np.ndarray,
        taken_c: np.ndarray,
        elapsed_c: np.ndarray,
        caps_c: Optional[np.ndarray],
        lane_ac: Optional[np.ndarray],
        lane_tc: Optional[np.ndarray],
        scalar_c_in: Optional[np.ndarray],
    ) -> int:
        """Shared tail of the bulk-ingest paths: wire-semantics
        classification (see ingest_deltas_batch) over a chunk whose rows
        are already assigned+pinned, then one queue append + wake-up.
        Returns deltas queued; unpins any it drops."""
        added_c = np.maximum(added_c, 0)
        taken_c = np.maximum(taken_c, 0)
        elapsed_c = np.maximum(elapsed_c, 0)
        # patrol-audit staleness stamp (remote absorb; racy by design).
        self.directory.last_remote_ns[rows] = self.clock()
        scalar_c = None
        if caps_c is not None:
            has_cap = caps_c >= 0
            # Adopt peer capacities first, so same-batch v1 deltas for
            # rows initialized here already see the base.
            self.directory.init_cap_base_many(
                rows[has_cap & (caps_c > 0)], caps_c[has_cap & (caps_c > 0)]
            )
            # v1 (no trailer) ⇒ capacity-included scalar aggregates; a
            # cap-less base trailer ⇒ raw own-lane header (no subtract).
            v1 = (
                ~has_cap & scalar_c_in
                if scalar_c_in is not None
                else np.zeros_like(has_cap)
            )
            base = self.directory.cap_base_nt[rows]
            sub = np.where(has_cap, np.maximum(caps_c, 0), np.where(v1, base, 0))
            added_c = np.maximum(added_c - sub, 0)
            lane_ok = np.zeros_like(has_cap)
            if lane_ac is not None:
                # Lane-trailer packets: the exact PN lane values replace
                # the header-derived approximation.
                lane_ok = has_cap & (lane_ac >= 0) & (lane_tc >= 0)
                added_c = np.where(lane_ok, lane_ac, added_c)
                taken_c = np.where(lane_ok, lane_tc, taken_c)
            # Deficit attribution for every aggregate-header delta: v1
            # packets and cap-without-lane trailers alike.
            scalar_c = v1 | (has_cap & ~lane_ok)
            # v1 deltas on rows with unknown capacity: drop (the peer's
            # next full-state broadcast re-delivers).
            unknown = v1 & (base == 0)
            if unknown.any():
                self._scalar_dropped += int(unknown.sum())
                self.directory.unpin_rows(rows[unknown])
                keep_c = ~unknown
                rows, slots_c = rows[keep_c], slots_c[keep_c]
                added_c, taken_c = added_c[keep_c], taken_c[keep_c]
                elapsed_c, scalar_c = elapsed_c[keep_c], scalar_c[keep_c]
                if not len(rows):
                    return 0
        absorbed_n = 0
        if HOST_FASTPATH:
            keep_h = self._host_absorb_ingest(
                rows, slots_c, added_c, taken_c, elapsed_c, scalar_c
            )
            if keep_h is not None and not keep_h.all():
                self.directory.unpin_rows(rows[~keep_h])
                absorbed_n = int((~keep_h).sum())
                rows, slots_c = rows[keep_h], slots_c[keep_h]
                added_c, taken_c = added_c[keep_h], taken_c[keep_h]
                elapsed_c = elapsed_c[keep_h]
                if scalar_c is not None:
                    scalar_c = scalar_c[keep_h]
                if not len(rows):
                    return absorbed_n
        chunk = _DeltaChunk(rows, slots_c, added_c, taken_c, elapsed_c, scalar_c)
        with self._cond:
            self._deltas.append(chunk)
            self._cond.notify()
        return chunk.n + absorbed_n

    def ingest_deltas_batch_raw(
        self,
        n: int,
        name_buf: np.ndarray,
        name_lens: np.ndarray,
        name_hashes: np.ndarray,
        slots: np.ndarray,
        added_nt: np.ndarray,
        taken_nt: np.ndarray,
        elapsed_ns: np.ndarray,
        caps_nt: np.ndarray,
        lane_added_nt: np.ndarray,
        lane_taken_nt: np.ndarray,
        scalar: np.ndarray,
    ) -> int:
        """Zero-materialization bulk ingest — the native rx loop's fast
        path. Names arrive as raw zero-padded byte rows + FNV hashes
        (native.decode_batch_raw); known buckets resolve through the
        directory's vectorized hash table without creating ONE Python
        string, and only directory misses (new buckets — once per bucket
        lifetime) fall back to string materialization and the evicting
        assign path. Wire-semantics classification is shared with
        :meth:`ingest_deltas_batch`. BENCH_r02 motivation: string
        materialization was 85% of decode cost on the replay bench."""
        now = self.clock()
        keep = (
            (slots[:n] >= 0)
            & (slots[:n] < self.config.nodes)
            & (name_lens[:n] >= 0)
        )
        idx_all = np.flatnonzero(keep)
        # Gather names as u64 words, not bytes: fancy-indexing cost scales
        # with element count (8× cheaper), and the directory verifies on
        # the same word view.
        name_words = np.ascontiguousarray(name_buf).view(np.uint64)
        accepted = 0
        for lo in range(0, len(idx_all), MAX_MERGE_ROWS):
            idx = idx_all[lo : lo + MAX_MERGE_ROWS]
            if not idx.size:
                continue
            rows = self.directory.lookup_hashed_pinned(
                name_hashes[idx], name_words[idx], name_lens[idx], now
            )
            miss = np.flatnonzero(rows < 0)
            if miss.size:
                mi = idx[miss]
                miss_rows = self._bind_wire_misses_pinned(
                    name_buf, name_lens, name_hashes, mi, now
                )
                if miss_rows is None:
                    hit = rows >= 0
                    idx, rows = idx[hit], rows[hit]
                    if not idx.size:
                        continue
                else:
                    rows[miss] = miss_rows
            accepted += self._classify_queue_chunk(
                rows,
                slots[idx].astype(np.int64),
                added_nt[idx],
                taken_nt[idx],
                elapsed_ns[idx],
                caps_nt[idx],
                lane_added_nt[idx],
                lane_taken_nt[idx],
                scalar[idx],
            )
        return accepted

    def _bind_wire_misses_pinned(
        self,
        name_buf: np.ndarray,
        name_lens: np.ndarray,
        hashes: np.ndarray,
        mi: np.ndarray,
        now: int,
    ) -> Optional[np.ndarray]:
        """Shared miss protocol of the wire ingest paths: materialize the
        first-seen names (the one place the rx path creates Python
        strings), bind + pin via the wire bind path. None ⇒ pool spent
        (logged); callers drop those deltas."""
        miss_names = [
            bytes(name_buf[i, : name_lens[i]]).decode("utf-8", "surrogateescape")
            for i in mi
        ]
        rows = self._assign_many_pinned_wire(
            miss_names, name_buf[mi], name_lens[mi], hashes[mi], now
        )
        if rows is None:
            log.warning("pool spent (all pinned); %d deltas dropped", mi.size)
        elif self.directory.has_tombstones():
            # Wire misses are creations by definition: re-seed any
            # reclaimed bucket's own lane from its tombstone.
            self._reseed_fresh_rows(
                miss_names, rows, np.ones(len(rows), dtype=bool)
            )
        return rows

    def ingest_wire_batch(
        self,
        dbuf,
        n: int,
        slots: np.ndarray,
        no_trailer: np.ndarray,
    ) -> int:
        """The native rx loop's fused fast path: raw decode buffers
        (native.DecodeBuffers — float64 wire headers, zero-padded name
        rows, FNV hashes) → classified device queue in ONE native call
        (pt_rx_classify: resolve + sanitize + wire-semantics classify).
        Python touches only the leftovers: directory misses (bound via the
        wire bind path, classified by the numpy tail) and v1 deltas whose
        row capacity was unknown at native classify time. Falls back to
        :meth:`ingest_deltas_batch_raw` when the native table is absent.
        BENCH r2/r3 motivation: the numpy classify tail cost ~500 ns/delta
        and capped host ingest around 1M deltas/s (VERDICT r2 item 2)."""
        now = self.clock()
        slots = np.ascontiguousarray(slots[:n], np.int64)
        res = self.directory.rx_classify(
            n, dbuf.hashes, dbuf.names, dbuf.name_lens, dbuf.added,
            dbuf.taken, dbuf.elapsed, slots, self.config.nodes,
            dbuf.caps, dbuf.lane_a, dbuf.lane_t, no_trailer, now,
        )
        if res is None:
            return self.ingest_deltas_batch_raw(
                n, dbuf.names, dbuf.name_lens, dbuf.hashes, slots,
                wire.sanitize_nt_array(dbuf.added[:n]),
                wire.sanitize_nt_array(dbuf.taken[:n]),
                np.maximum(dbuf.elapsed[:n].astype(np.int64), 0),
                dbuf.caps[:n], dbuf.lane_a[:n], dbuf.lane_t[:n],
                no_trailer[:n].astype(bool),
            )
        rows, out_a, out_t, out_e, out_s = res
        accepted = 0
        miss = rows == -1
        if miss.any():
            # First sight of these buckets (once per bucket lifetime):
            # bind, then classify through the numpy tail.
            mi = np.flatnonzero(miss)
            miss_rows = self._bind_wire_misses_pinned(
                dbuf.names, dbuf.name_lens, dbuf.hashes, mi, now
            )
            if miss_rows is not None:
                accepted += self._classify_queue_chunk(
                    miss_rows,
                    slots[mi],
                    wire.sanitize_nt_array(dbuf.added[mi]),
                    wire.sanitize_nt_array(dbuf.taken[mi]),
                    np.maximum(dbuf.elapsed[mi].astype(np.int64), 0),
                    dbuf.caps[mi],
                    dbuf.lane_a[mi],
                    dbuf.lane_t[mi],
                    no_trailer[mi].astype(bool),
                )
        live = rows >= 0
        recheck = live & (out_s == 2)
        if recheck.any():
            # v1 deltas on rows whose capacity was 0 during the native
            # pass; the miss binds above may have adopted caps since.
            idx2 = np.flatnonzero(recheck)
            base = self.directory.cap_base_nt[rows[idx2]]
            known = base > 0
            ki = idx2[known]
            out_a[ki] = np.maximum(out_a[ki] - base[known], 0)
            out_s[ki] = 1
            drop = idx2[~known]
            if drop.size:
                self._scalar_dropped += int(drop.size)
                self.directory.unpin_rows(rows[drop])
                live[drop] = False
        idx = np.flatnonzero(live)
        for lo in range(0, len(idx), MAX_MERGE_ROWS):
            sl = idx[lo : lo + MAX_MERGE_ROWS]
            if HOST_FASTPATH:
                keep_h = self._host_absorb_ingest(
                    rows[sl], slots[sl], out_a[sl], out_t[sl], out_e[sl],
                    out_s[sl] == 1,
                )
                if keep_h is not None and not keep_h.all():
                    self.directory.unpin_rows(rows[sl][~keep_h])
                    accepted += int((~keep_h).sum())
                    sl = sl[keep_h]
                    if not sl.size:
                        continue
            chunk = _DeltaChunk(
                rows[sl], slots[sl], out_a[sl], out_t[sl], out_e[sl],
                out_s[sl] == 1,
            )
            with self._cond:
                self._deltas.append(chunk)
                self._cond.notify()
            accepted += chunk.n
        return accepted

    def read_rows(self, rows) -> tuple:
        """Donation-safe gather of per-bucket state: returns (pn[K,N,2],
        elapsed[K]) as host numpy arrays. The gather is padded to a
        power-of-two so arbitrary row counts don't each JIT a new variant."""
        rows = np.asarray(rows, dtype=np.int32)
        n = len(rows)
        k = _pad_size(n, lo=1, hi=1 << 20)
        padded = np.zeros(k, dtype=np.int32)
        padded[:n] = rows
        idx = jnp.asarray(padded)
        with self._state_mu:
            rs = read_rows(self.state, idx)
            # THE sanctioned gather seam: one batched D2H per call. The
            # scrape surfaces (snapshot/row_view/debug vars) answer from
            # the epoch-validated host mirror and only land here on a
            # mirror miss.
            return (
                np.asarray(rs.pn)[:n],  # patrol-lint: disable=PTD003
                np.asarray(rs.elapsed)[:n],  # patrol-lint: disable=PTD003
            )

    def _scrape_epoch(self) -> Tuple[int, int]:
        """The device-state version a mirror snapshot is stamped with.
        Plain int reads (GIL-atomic): a bump landing mid-read only makes
        the mirror LOOK stale — never lets stale data serve as fresh."""
        return (self._ticks, self._state_gen)

    def _refresh_scrape_mirror(self) -> None:
        """One batched window gather re-stamping the scrape mirror. The
        epoch is captured BEFORE the gather: a mutation racing the
        gather leaves the mirror stamped older than its data, which only
        costs an extra refresh — stamping after could mark pre-mutation
        data as current."""
        k = self._mirror_window
        if k <= 0:
            return
        epoch = self._scrape_epoch()
        pn, elapsed = self.read_rows(np.arange(k, dtype=np.int32))
        self._scrape_mirror = (epoch, pn, elapsed)
        profiling.COUNTERS.inc("scrape_mirror_refreshes")

    def _scrape_rows(self, rows) -> Tuple[np.ndarray, np.ndarray]:
        """(pn[len,N,2], elapsed[len]) for device rows on the STATS path
        (snapshot/tokens//debug/vars): answered from the host mirror
        whenever its epoch still matches — exact, zero device transfers
        — else one window gather re-arms it. Rows beyond the mirror
        window (or with the mirror disabled) fall back to a targeted
        gather. The serve path never calls this; ticket completion reads
        ride the completer's batched readback."""
        rows = np.asarray(rows, dtype=np.int32)
        if SCRAPE_MIRROR and rows.size and int(rows.max()) < self._mirror_window:
            mir = self._scrape_mirror
            if mir is None or mir[0] != self._scrape_epoch():
                # Stale: flag interest so the completion pipeline keeps
                # the mirror hot while load is flowing, and re-arm it
                # here so an IDLE engine converges to zero-gather
                # scrapes immediately.
                self._mirror_want = True
                self._refresh_scrape_mirror()
                mir = self._scrape_mirror
            if mir is not None:
                profiling.COUNTERS.inc("scrape_mirror_hits")
                epoch, pn, elapsed = mir
                return pn[rows], elapsed[rows]
        profiling.COUNTERS.inc("scrape_device_gathers")
        return self.read_rows(rows)

    def _hosted_view(self, row: int):
        """(pn[N,2] copy, elapsed_ns) if the row is host-resident, else
        None. Snapshot-consistent: copied under the host lock."""
        if not (HOST_FASTPATH and self._hosted_flag[row]):
            return None
        with self._host_mu:
            lanes = self._hosted.get(row)
            if lanes is None:
                return None
            return (
                np.stack([lanes.added, lanes.taken], axis=-1),
                lanes.elapsed_ns,
            )

    def row_view(self, row: int) -> Tuple[np.ndarray, int]:
        """One bucket row's full PN state, wherever it lives: host lanes
        for host-resident rows, a device gather otherwise."""
        hv = self._hosted_view(row)
        if hv is not None:
            return hv
        pn_rows, elapsed_rows = self._scrape_rows([row])
        return pn_rows[0], int(elapsed_rows[0])

    def snapshot(self, name: str) -> List[wire.WireState]:
        """Read one bucket's full PN state as per-slot wire states — the
        incast reply payload (repo.go:86-90): one packet per non-zero node
        lane, each tagged with its origin slot."""
        row = self.directory.lookup(name)
        if row is None:
            return []
        hv = self._hosted_view(row)
        if hv is not None:
            # Same re-lookup the device branch does: the row could have
            # been evicted and re-bound (and re-HOSTED by another name's
            # take) between the lookup and the view.
            if self.directory.lookup(name) != row:
                return []
            pn, elapsed = hv
        else:
            pn_rows, elapsed_rows = self._scrape_rows([row])
            if self.directory.lookup(name) != row:
                return []  # evicted mid-read
            pn = pn_rows[0]  # [N, 2]
            elapsed = int(elapsed_rows[0])
        cap = int(self.directory.cap_base_nt[row])
        sum_a = int(pn[:, 0].sum())
        sum_t = int(pn[:, 1].sum())
        out = []
        for slot in range(pn.shape[0]):
            a, t = int(pn[slot, 0]), int(pn[slot, 1])
            if a or t:
                # Dual payload (ops/wire.py): aggregate scalars in the
                # header (what reference peers max-merge, idempotent across
                # the per-lane packets), exact lane values in the trailer.
                out.append(
                    wire.from_nanotokens(
                        name, cap + sum_a, sum_t, elapsed,
                        origin_slot=slot, cap_nt=cap,
                        lane_added_nt=a, lane_taken_nt=t,
                    )
                )
        if not out and (elapsed or cap):
            out.append(
                wire.from_nanotokens(
                    name, cap, 0, elapsed, origin_slot=self.node_slot,
                    cap_nt=cap, lane_added_nt=0, lane_taken_nt=0,
                )
            )
        return out

    def release_bucket(self, name: str, timeout: float = 5.0) -> bool:
        """Evict one bucket by name: unbind, zero its device row, recycle.
        The bucket's state survives on peers and re-hydrates via incast on
        next use — the same soft-state story as a node restart (SURVEY §5).
        Unbind-before-zero ordering (the eviction protocol's limbo phase)
        keeps a concurrently re-created bucket from seeing stale state, and
        a pinned row (in-flight take/delta) is waited out, never yanked."""
        deadline = time.monotonic() + timeout
        with self._evict_mu:
            while True:
                row, bound = self.directory.unbind_if_unpinned(name)
                if row is not None:
                    break
                if not bound:
                    return False
                self.flush(timeout=max(0.0, deadline - time.monotonic()))
                if time.monotonic() >= deadline:
                    return False
            self._drop_hosted_rows([row])
            with self._state_mu:
                self.state = zero_rows_jit(
                    self.state, jnp.array([row], jnp.int32)
                )
                self._state_gen += 1
            self.directory.recycle([row])
        return True

    def snapshot_many(self, names: Sequence[str]) -> Dict[str, List[wire.WireState]]:
        """Batched :meth:`snapshot`: one device gather for many buckets
        (the incast-reply fan-in under cold-key storms); host-resident rows
        answer from their lanes without touching the device."""
        known = [(n, self.directory.lookup(n)) for n in names]
        known = [(n, r) for n, r in known if r is not None]
        if not known:
            return {}
        hosted_views = {
            r: hv for _, r in known if (hv := self._hosted_view(r)) is not None
        }
        device_rows = [r for _, r in known if r not in hosted_views]
        if device_rows:
            pn_dev, el_dev = self._scrape_rows(device_rows)
            dev_at = {r: i for i, r in enumerate(device_rows)}
        out: Dict[str, List[wire.WireState]] = {}
        for name, row in known:
            if self.directory.lookup(name) != row:
                continue  # evicted mid-read: don't leak another bucket's state
            hv = hosted_views.get(row)
            if hv is not None:
                pn, elapsed = hv
            else:
                pn = pn_dev[dev_at[row]]
                elapsed = int(el_dev[dev_at[row]])
            cap = int(self.directory.cap_base_nt[row])
            sum_a = int(pn[:, 0].sum())
            sum_t = int(pn[:, 1].sum())
            states = [
                wire.from_nanotokens(
                    name, cap + sum_a, sum_t, elapsed,
                    origin_slot=s, cap_nt=cap,
                    lane_added_nt=int(pn[s, 0]), lane_taken_nt=int(pn[s, 1]),
                )
                for s in range(pn.shape[0])
                if pn[s, 0] or pn[s, 1]
            ]
            if not states and (elapsed or cap):
                states = [
                    wire.from_nanotokens(
                        name, cap, 0, elapsed, origin_slot=self.node_slot,
                        cap_nt=cap, lane_added_nt=0, lane_taken_nt=0,
                    )
                ]
            if states:
                out[name] = states
        return out

    def tokens(self, name: str) -> int:
        """Whole tokens currently in a bucket (introspection; bucket.go:156)."""
        return self.tokens_if_known(name) or 0

    def tokens_if_known(self, name: str) -> Optional[int]:
        """Balance with existence: ``None`` for an unknown bucket, else the
        whole-token balance. The post-read re-lookup closes the eviction
        race (same pattern as :meth:`snapshot`): without it, a concurrent
        evict-and-rebind between lookup and the device gather could
        return another bucket's balance under this name."""
        row = self.directory.lookup(name)
        if row is None:
            return None
        hv = self._hosted_view(row)
        if hv is not None:
            if self.directory.lookup(name) != row:
                return None  # evicted and re-bound (possibly re-hosted)
            pn = hv[0]
        else:
            pn_rows, _ = self._scrape_rows([row])
            if self.directory.lookup(name) != row:
                return None  # evicted (and possibly rebound) mid-read
            pn = pn_rows[0]
        base = int(self.directory.cap_base_nt[row])
        nt = base + int(pn[:, 0].sum()) - int(pn[:, 1].sum())
        return max(nt, 0) // NANO

    def _warm_calls(self) -> List[tuple]:
        """Every padded kernel variant serving can dispatch, as
        ``(jitted, args after the state, state_out)``: ``state_out`` says
        where the call's result carries the new state — "all" (the
        result is the state), 0 (its first element), None (a pure read).
        """
        def pow2(lo: int, hi: int):
            size = lo
            while size <= hi:
                yield size
                size <<= 1

        i64 = jnp.int64
        calls: List[tuple] = []
        take = _jit_take_packed(self.node_slot)
        calls += [(take, (jnp.zeros((8, k), i64),), 0) for k in pow2(8, MAX_TAKE_ROWS)]
        # The merge tick: the folded scatter, the fold-to-dense row
        # windows, and the coalesced commit ring (one variant per
        # power-of-two block count the drain can coalesce).
        folded = _jit_merge_folded()
        for k in pow2(8, MAX_MERGE_ROWS):
            packed = np.zeros((6, k), np.int64)
            packed[0] = _FOLD_PAD_ROW
            packed[1] = np.arange(k)
            packed[4] = _FOLD_PAD_ROW + np.arange(k)
            calls.append((folded, (jnp.asarray(packed),), "all"))
        dense = _jit_merge_rows_dense()
        calls += [
            (
                dense,
                (
                    jnp.full((k,), _FOLD_PAD_ROW, i64) + jnp.arange(k, dtype=i64),
                    jnp.zeros((k, self.config.nodes, 2), i64),
                    jnp.zeros((k,), i64),
                ),
                "all",
            )
            for k in pow2(8, MAX_ROW_DENSE)
        ]
        commit = _jit_commit_packed()
        for j in pow2(2, self._commit_blocks):
            warm = commit_mod.pack_commit_blocks(
                *(np.empty(0, np.int64) for _ in range(6)),
                MAX_MERGE_ROWS,
                out=np.empty((6, j, MAX_MERGE_ROWS), np.int64),
            )
            calls.append((commit, (jnp.asarray(warm),), "all"))
        # Snapshot/introspection gathers (engine.read_rows pads to these).
        calls += [(read_rows, (jnp.zeros(k, jnp.int32),), None) for k in pow2(1, 1024)]
        # Lifecycle sweep probes: the GC cadence must never JIT mid-serve
        # while holding _state_mu (cap 0 padding: never reports full).
        hi = _pad_size(self._gc_sweep_max, lo=8, hi=1 << 20)
        calls += [
            (
                lifecycle_ops.lifecycle_probe_jit,
                (lifecycle_ops.LifecycleProbe(
                    rows=jnp.zeros(k, jnp.int32),
                    now_ns=jnp.zeros(k, i64),
                    per_ns=jnp.zeros(k, i64),
                    cap_base_nt=jnp.zeros(k, i64),
                    created_ns=jnp.zeros(k, i64),
                ), self.node_slot),
                None,
            )
            for k in pow2(8, hi)
        ]
        if self._raw_ingest_capable:
            # Raw-plane ingest, one variant per padded plane count, or the
            # first replication burst of each size compiles on the rx path
            # while the ring backs up.
            from patrol_tpu.ops import ingest as ingest_ops

            row = ingest_ops.RAW_PLANE_BYTES
            e = ingest_ops.max_entries(row)
            calls += [
                (
                    ingest_ops.decode_fold_raw_jit,
                    (
                        jnp.zeros((p, row), jnp.uint8),
                        jnp.zeros(p, jnp.int32),
                        jnp.zeros((p, e), jnp.int32),
                        jnp.full((p, e), _FOLD_PAD_ROW, jnp.int32),
                        jnp.zeros((p, e), jnp.bool_),
                    ),
                    0,
                )
                for p in pow2(1, ingest_ops.RAW_MAX_PLANES)
            ]
        return calls

    def warmup(self) -> None:
        """Pre-compile every padded kernel variant (take and merge at each
        power-of-two batch size) so production traffic never pays a JIT
        compile: without this, the first request that widens the batch
        stalls its whole tick (seen as multi-100ms p99.9 spikes).

        The variants first compile side by side on a thread pool (compiles
        are independent and release the GIL: the cold boot at 1M x 256
        took 532 s one after another on a v5e host); each then runs once,
        and that call finds the executable its compile left in JAX's
        in-process cache (and in the persistent one, when set)."""
        from concurrent.futures import ThreadPoolExecutor

        calls = self._warm_calls()

        def compile_one(call) -> None:
            fn, args, _ = call
            fn.lower(self.state, *args).compile()

        with ThreadPoolExecutor(max(1, min(8, os.cpu_count() or 1))) as pool:
            list(pool.map(compile_one, calls))
        for fn, args, state_out in calls:
            with self._state_mu:
                out = fn(self.state, *args)
                if state_out == "all":
                    self.state = out
                elif state_out == 0:
                    self.state = out[0]
        jax.block_until_ready(self.state.pn)

    def flush(self, timeout: float = 5.0) -> bool:
        """Block until all currently queued work has been applied to device
        state AND every completion has fanned out. Test/introspection
        helper, not a hot-path call."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._cond:
                idle = (
                    not self._takes
                    and not self._deltas
                    and not self._promote_pending
                    and not self._busy
                )
            if idle:
                with self._pcond:
                    if not self._pending and not self._completing:
                        return True
            time.sleep(0.0005)
        return False

    def stop(self) -> None:
        from patrol_tpu.utils import slo as slo_mod

        slo_mod.SENTINEL.unwatch_budget(self._budget_snapshot)
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        with self._pcond:
            # Wake a feeder parked in _enqueue_completion back-pressure NOW
            # (not after its 5s join) so the graceful drain can finish.
            self._pcond.notify_all()
        # _feeder_done is set by the feeder's own exit path (_run), never
        # here: a timed-out join must not let the completer quit while the
        # drain is still producing ticks (stranded tickets, leaked pins).
        self._thread.join(timeout=5)
        self._completer.join(timeout=5)
        if self._native_store is not None:
            # The HTTP front must already be detached (command.py closes
            # the front before engine.stop). Frees every lane block — so
            # drop every proxy first; host-lane views are invalid from
            # here and post-stop introspection sees device planes only.
            with self._host_mu:
                self._hosted.clear()
                self._promoting.clear()
                self._hosted_flag[:] = False
            store, self._native_store = self._native_store, None
            if getattr(self, "_leak_native_store", False):
                # A wedged front pump may still be inside the store
                # (native_http.close's leaked-server path): leak the
                # blocks rather than free them under a live thread.
                log.error("leaking native host store (wedged http pump)")
            else:
                store.destroy()
        self.directory.close()  # releases the native resolve table

    # -- completion pipeline ------------------------------------------------

    def _enqueue_completion(self, thunk, keys, groups) -> None:
        """Hand a tick's completion to the completer thread. Only the
        grouped (non-deferred) tickets belong to the tick — deferred ones
        are already re-queued and must never be failed here — so the
        flatten lives in this one place. Bounded: a slow completer
        back-pressures dispatch rather than buffering device results
        without limit."""
        tickets = [t for key in keys for t in groups[key]]
        with self._pcond:
            while len(self._pending) >= self._dispatch_ahead and not self._stopped:
                self._pcond.wait()
            self._pending.append((thunk, tickets))
            depth = len(self._pending) + (1 if self._completing else 0)
            self._pcond.notify_all()
        profiling.COUNTERS.set_max("dispatch_ahead_depth", depth)

    def _complete_loop(self) -> None:
        while True:
            with self._pcond:
                # Exit only when the FEEDER is done dispatching AND every
                # pending completion ran: the feeder's graceful drain keeps
                # producing ticks after _stopped is set, and abandoning one
                # would hang its callers with their row pins leaked.
                while not self._pending and not self._feeder_done:
                    self._pcond.wait()
                if not self._pending:
                    return  # feeder exited and the queue is drained
                thunk, tickets = self._pending.popleft()
                self._completing = True
                self._pcond.notify_all()  # wake a back-pressured feeder
            try:
                t0 = time.perf_counter_ns()
                with trace_mod.TRACE.span(
                    trace_mod.EV_COMMIT_COMPLETE, len(tickets)
                ):
                    thunk()
                hist.STAGE_COMPLETION.record(time.perf_counter_ns() - t0)
            except Exception:  # pragma: no cover - completer must not die
                log.exception("tick completion failed")
                try:
                    self._fail_tickets(tickets)
                except Exception:
                    log.exception("ticket failure fan-out failed")
            finally:
                with self._pcond:
                    self._completing = False
                    self._pcond.notify_all()
            if SCRAPE_MIRROR and self._mirror_want:
                # Scrapes are active and went stale under load: re-arm
                # the mirror HERE, off the scrape threads, so the next
                # stats read costs zero transfers. One window gather per
                # completion batch, and only while scrape interest is
                # flagged.
                try:
                    self._refresh_scrape_mirror()
                    self._mirror_want = False
                except Exception:  # pragma: no cover - gauge refresh
                    log.exception("scrape-mirror refresh failed")

    @property
    def ticks(self) -> int:
        return self._ticks

    @property
    def evictions(self) -> int:
        return self._evictions

    @property
    def scalar_dropped(self) -> int:
        """v1 (reference-peer) deltas dropped while the row's capacity was
        unknown — re-delivered by the peer's next full-state broadcast."""
        return self._scalar_dropped

    @property
    def hosted_buckets(self) -> int:
        """Buckets currently served by the host fast path."""
        return len(self._hosted)

    @property
    def host_takes(self) -> int:
        """Takes answered in-process by the host fast path (µs-class):
        Python-served plus C++-in-front-served."""
        n = self._host_takes
        if self._native_store is not None:
            n += self._native_store.native_takes
        return n

    @property
    def promotions(self) -> int:
        """Host→device residency transitions (QPS threshold or rx traffic)."""
        return self._promotions

    @property
    def demotions(self) -> int:
        """Device→host residency transitions (idle window under crossover)."""
        return self._demotions

    @property
    def audit_ledger(self) -> AuditLedger:
        """patrol-audit admitted-token window ledger (net/audit.py reads
        it on the audit plane's pace)."""
        return self._audit

    def audit_staleness_samples(self, limit: int = 64) -> List[int]:
        """Per-bucket staleness sample for the audit plane: ns the last
        local emission ran ahead of the last remote absorb, over up to
        ``limit`` buckets that have seen both."""
        return [int(v) for v in self.directory.staleness_sample(limit)]

    @property
    def pending_completions(self) -> int:
        """Dispatched ticks whose results haven't fanned out yet — the
        completion pipeline's depth (backpressure signal)."""
        with self._pcond:
            return len(self._pending) + (1 if self._completing else 0)

    def backlog(self) -> int:
        """Queued-but-unapplied work rows (takes + deltas, counting each
        delta inside a bulk chunk): the public backpressure signal for bulk
        feeders (bench replay, heal ingest)."""
        with self._cond:
            return sum(
                len(t.tickets) if isinstance(t, _TakeFold) else 1
                for t in self._takes
            ) + sum(
                d.n if isinstance(d, _DeltaChunk) else 1 for d in self._deltas
            )

    @property
    def tick_busy(self) -> bool:
        """A drained tick is still applying (compiling, dispatching): its
        work has left the queues that :meth:`backlog` counts."""
        with self._cond:
            return self._busy

    # -- engine loop --------------------------------------------------------

    def _run(self) -> None:
        try:
            self._run_loop()
        finally:
            with self._pcond:
                # The feeder itself declares dispatch over — stop() cannot,
                # because its 5s join may time out while the drain is still
                # producing ticks, and a flag set too early (or never) either
                # strands enqueued completions or parks the completer forever.
                self._feeder_done = True
                self._pcond.notify_all()

    def _run_loop(self) -> None:
        while True:
            with self._cond:
                # Single predicate — pause and work-availability re-check
                # together on every wake, so a pause raised while this
                # thread waits for work can never be skipped (two
                # sequential loops would have that race).
                while (self._tick_paused and not self._stopped) or not (
                    self._takes
                    or self._deltas
                    or self._promote_pending
                    or self._gc_due
                    or self._stopped
                ):
                    self._cond.wait()
                if self._stopped and not (self._takes or self._deltas):
                    return
                # The tick's span: drain through dispatch (its cause is
                # the oldest pump batch it drained).
                tick = trace_mod.TRACE.span(trace_mod.EV_TICK).begin()
                self._gc_due = False  # this tick runs _maybe_gc below
                # Drain up to _commit_blocks blocks per tick: everything
                # past one block's budget coalesces into a single commit
                # dispatch (_commit_coalesced) instead of riding extra
                # ticks — one transfer + one dispatch either way. In
                # auto mode the block count tracks the backlog, capped
                # by the measured per-row device-commit cost.
                if self._commit_blocks_auto:
                    self._auto_size_commit_blocks_locked()
                deltas = self._drain_deltas(
                    MAX_MERGE_ROWS * self._commit_blocks
                )
                tickets = self._drain_takes(MAX_TAKE_ROWS)
                # The tick's busy time and CPU run from here to its end
                # (feeder_busy_ns, feeder_cpu_ns).
                t_drain = time.perf_counter_ns()
                cpu_drain = time.thread_time_ns()
                # Clear the re-queue marker at drain time, not in
                # _group_tickets: if the tick dies before grouping runs, a
                # stale True from a prior tick would make _fail_tickets skip
                # the ticket and hang its caller while leaking the row pin.
                # A ticket drained for the first time closes its queue
                # wait (take_queue_ns) and opens its take stages.
                queued_t0 = []
                oldest = 0
                for t in tickets:
                    if t.deferred:
                        t.deferred = False
                        t.drain_ns = 0
                    else:
                        t.drain_ns = t_drain
                        queued_t0.append(t.t0_ns)
                    if t.batch and (not oldest or t.batch < oldest):
                        oldest = t.batch
                self._busy = True
            tick.cause = oldest
            if queued_t0:
                hist.TAKE_QUEUE.record_many([t_drain - t0 for t0 in queued_t0])
            # Idle demotion: count device-path takes on promoted rows and,
            # at window rollover, move quiet promoted rows back to host
            # residency BEFORE the re-route — so the very take that ends an
            # idle window is already host-served (sub-ms again, VERDICT r4
            # item 3's config #1-after-a-burst scenario).
            if HOST_FASTPATH and self._demotion_capable and self._promoted_rows:
                for t in tickets:
                    if t.row in self._promoted_rows:
                        self._dev_window[t.row] = (
                            self._dev_window.get(t.row, 0) + 1
                        )
                self._maybe_demote(tickets, deltas)
            # Bucket lifecycle: sweep full idle buckets at the GC window
            # cadence (pressure ramps it 8x). In-hand work is safe by
            # construction — this tick's deltas and tickets hold pins.
            self._maybe_gc()
            # Residency re-route: a ticket that raced into the device queue
            # while its row was (or became) host-resident is served from
            # the host model here — the one point every queued take passes
            # through, so a row is never served by both paths at once.
            if HOST_FASTPATH and self._hosted and tickets:
                bc: List[wire.WireState] = []
                served: List[TakeTicket] = []
                kept: List[TakeTicket] = []
                for t in tickets:
                    if self._hosted_flag[t.row] and self._host_serve_ticket(
                        t, False, bc
                    ):
                        served.append(t)
                    else:
                        kept.append(t)
                tickets = kept
                hand_back(served)
                self._emit_broadcasts(bc)
            t_tick0 = time.perf_counter_ns()
            try:
                # Pending promotions join BEFORE the tick's device work,
                # so a take routed device-ward this tick (its row's flag
                # flipped in the drain above or earlier) always runs
                # against the already-joined planes.
                if HOST_FASTPATH and self._promote_pending:
                    self._drain_promotions()
                # The re-route may have served everything: don't dispatch
                # an all-padding device step (a wasted full round trip —
                # and on MeshEngine a whole fused no-op step).
                if deltas is not None or tickets:
                    self._apply(deltas, tickets)
                    tick_dur = time.perf_counter_ns() - t_tick0
                    tick.arg = (
                        len(deltas) if deltas is not None else 0
                    ) + len(tickets)
                    for tid, tname in self._tick_traced:
                        # Remote deltas merged this tick: their merge
                        # spans close here, joined by the propagated id.
                        trace_mod.SPANS.add(
                            tid, self.node_slot, "merge", tname,
                            t_tick0, tick_dur,
                        )
            except Exception:  # pragma: no cover - engine must never die
                log.exception("engine tick failed")
                profiling.COUNTERS.inc("engine_tick_failures")
                trace_mod.anomaly("engine-tick-failed")
                self._fail_tickets(tickets)
            finally:
                self._tick_traced = []
                if deltas is not None:
                    # Deltas are done (applied or lost with the tick): their
                    # in-flight row pins release here, success or not.
                    self.directory.unpin_rows(deltas.rows)
                with self._cond:
                    self._busy = False
                profiling.COUNTERS.inc(
                    "feeder_busy_ns", time.perf_counter_ns() - t_drain
                )
                profiling.COUNTERS.inc(
                    "feeder_cpu_ns", time.thread_time_ns() - cpu_drain
                )
                tick.end()

    @staticmethod
    def _drain(q: deque, limit: int) -> list:
        out = []
        while q and len(out) < limit:
            out.append(q.popleft())
        return out

    def _drain_takes(self, limit: int) -> List[TakeTicket]:
        """Pop up to ``limit`` take-queue ENTRIES (caller holds
        ``_cond``) and return the FLAT ticket list in arrival order. A
        folded hot-key entry counts ONCE against the limit — it becomes
        one packed row — so a coalesced tick can serve far more tickets
        than the row budget; popping an entry closes its fold, and later
        arrivals for the key open a fresh one."""
        out: List[TakeTicket] = []
        q = self._takes
        n = 0
        while q and n < limit:
            item = q.popleft()
            n += 1
            if isinstance(item, _TakeFold):
                if self._open_folds.get(item.key) is item:
                    del self._open_folds[item.key]
                out.extend(item.tickets)
            else:
                out.append(item)
        return out

    def _auto_size_commit_blocks_locked(self) -> None:
        """Adaptive commit-block sizing (PATROL_COMMIT_BLOCKS=auto;
        caller holds ``_cond``). The drain width tracks the queue
        backlog — light load drains one block per tick (lowest latency),
        a flood coalesces toward COMMIT_BLOCKS_MAX — and the completion
        pipeline's measured per-row device-commit cost caps the width so
        one dispatch's completion never exceeds PATROL_COMMIT_BUDGET_MS.
        Tascade's lesson (arXiv:2311.15810) with a governor: coalescing
        beats per-update commits, but only up to the latency budget."""
        backlog = sum(
            d.n if isinstance(d, _DeltaChunk) else 1 for d in self._deltas
        )
        want = max(1, -(-backlog // MAX_MERGE_ROWS)) if backlog else 1
        want = min(want, COMMIT_BLOCKS_MAX)
        ewma = self._commit_row_ns_ewma
        if ewma > 0.0:
            budget_blocks = max(
                1, int(COMMIT_BUDGET_NS / (ewma * MAX_MERGE_ROWS))
            )
            want = min(want, budget_blocks)
        if want != self._commit_blocks:
            self._commit_blocks = want
            profiling.COUNTERS.inc("commit_blocks_auto_resized")

    def _drain_deltas(self, limit: int) -> Optional[DeltaArrays]:
        """Pop queued deltas (singles and pre-vectorized chunks) up to a row
        budget, concatenated into flat arrays in arrival order. Called under
        ``_cond``. A chunk is never split; one oversized-first chunk may
        exceed the budget alone."""
        q = self._deltas
        items: list = []
        total = 0
        while q:
            n = q[0].n if isinstance(q[0], _DeltaChunk) else 1
            if total and total + n > limit:
                break
            items.append(q.popleft())
            total += n
        if not items:
            return None
        rows = np.empty(total, np.int64)
        slots = np.empty(total, np.int64)
        added = np.empty(total, np.int64)
        taken = np.empty(total, np.int64)
        elapsed = np.empty(total, np.int64)
        scalar = np.zeros(total, bool)
        traced = self._tick_traced = []
        at = 0
        for it in items:
            if isinstance(it, _DeltaChunk):
                rows[at : at + it.n] = it.rows
                slots[at : at + it.n] = it.slots
                added[at : at + it.n] = it.added_nt
                taken[at : at + it.n] = it.taken_nt
                elapsed[at : at + it.n] = it.elapsed_ns
                scalar[at : at + it.n] = it.scalar
                at += it.n
            else:
                rows[at] = it.row
                slots[at] = it.slot
                added[at] = it.added_nt
                taken[at] = it.taken_nt
                elapsed[at] = it.elapsed_ns
                scalar[at] = it.scalar
                if it.trace_id:
                    traced.append((it.trace_id, it.trace_name))
                at += 1
        return DeltaArrays(rows, slots, added, taken, elapsed, scalar)

    def _fail_tickets(self, tickets: Sequence[TakeTicket]) -> None:
        unpin = [
            t.row for t in tickets if not t.deferred and t.complete(0, False)
        ]
        hand_back(tickets)
        if unpin:
            self.directory.unpin_rows(unpin)

    def _apply(self, deltas: Optional[DeltaArrays], tickets: Sequence[TakeTicket]) -> None:
        """One tick's work. Subclasses may fuse both phases into a single
        device call (MeshEngine)."""
        if deltas is not None:
            self._apply_merges(deltas)
        if tickets:
            self._apply_takes(tickets)

    def _group_tickets(self, tickets: Sequence[TakeTicket]):
        """Coalesce by (row, rate, count) preserving arrival order; defer
        rows seen with a second key to the next tick (kernel invariant:
        unique rows per batch). → (keys, groups).

        Starvation bound (rate-diversity adversary): deferred tickets are
        re-queued at the FRONT in arrival order, so a ticket can only wait
        behind same-row tickets that arrived BEFORE it — one tick per
        distinct earlier key, and never behind later arrivals. A client
        hammering one bucket with N distinct rates therefore delays only
        that bucket, by exactly its own queue depth (the same cost any
        FIFO service gives N requests), and cannot push an
        already-queued victim back (pinned by
        tests/test_engine.py::TestRateDiversity)."""
        # PATROL_TAKE_FOLD=0 — the per-ticket replay reference: every
        # ticket rides its own nreq=1 row, so a row's second ticket
        # defers to the next tick (the kernel invariant of unique rows
        # per batch stands either way). This is the pre-coalescing
        # serving discipline the bench's hot-key leg replays against.
        per_ticket = not _take_fold_enabled()
        groups: Dict[tuple, List[TakeTicket]] = {}
        row_key: Dict[int, tuple] = {}
        deferred: List[TakeTicket] = []
        for t in tickets:
            key = (t.row, t.rate.freq, t.rate.per_ns, t.count)
            held = row_key.get(t.row)
            if held is None:
                row_key[t.row] = key
                groups[key] = [t]
            elif held == key and not per_ticket:
                groups[key].append(t)
            else:
                deferred.append(t)
        if deferred:
            for t in deferred:
                t.deferred = True
            with self._cond:
                self._takes.extendleft(reversed(deferred))
                self._cond.notify()
        return list(groups.keys()), groups

    def _complete_groups(
        self, keys, groups, have, admitted, own_a, own_t, elapsed, sum_a, sum_t
    ) -> Tuple[int, int]:
        """Fan per-group kernel results out to tickets + broadcast hook.
        Completion releases each ticket's directory pin. Every ticket
        completes, and the native front's answers among them are handed
        back, before the tick's audit notes and broadcasts are built.
        → (the completion stamp, how many tickets completed here had
        their queue wait closed by this tick's drain: those whose take
        stages tile their ``take_service_ns``)."""
        broadcasts: List[wire.WireState] = []
        unpin: List[int] = []
        drained = 0
        done_ns = time.perf_counter_ns()
        now_clock = self.clock()
        take_hist = self._take_service
        admitted_nts: List[int] = []
        for i, key in enumerate(keys):
            ts = groups[key]
            c_nt = ts[0].count * NANO
            admitted_nt = 0
            adm = int(admitted[i])
            if 0 < adm < len(ts):
                # A coalesced row whose grant covered only a prefix:
                # the earliest tickets are admitted, the rest get clean
                # denies (split_grant's FIFO discipline).
                profiling.COUNTERS.inc("take_partial_grants")
            outcomes = split_grant(int(have[i]), adm, c_nt, len(ts))
            for t, (remaining, ok) in zip(ts, outcomes):
                if ok:
                    admitted_nt += c_nt
                if not t.done_ns:
                    t.done_ns = done_ns
                if t.complete(remaining, ok):
                    unpin.append(t.row)
                    take_hist.record(done_ns - t.t0_ns)
                    if t.drain_ns:
                        drained += 1
                    if t.trace_id:
                        trace_mod.SPANS.add(
                            t.trace_id, self.node_slot, "take", t.name,
                            t.t0_ns, done_ns - t.t0_ns,
                        )
            admitted_nts.append(admitted_nt)
        hand_back(t for key in keys for t in groups[key])
        for i, key in enumerate(keys):
            ts = groups[key]
            admitted_nt = admitted_nts[i]
            # Replicate. The reference broadcasts full state on every take,
            # success or not (api.go:74, README.md:41-43) — even a failed
            # first take commits the lazy capacity init (bucket.go:194-196),
            # which we mirror. Dual payload (ops/wire.py): the float header
            # carries the aggregate scalar view (cap + Σadded, Σtaken) that
            # reference peers max-merge; the trailer carries this node's
            # exact PN lane for patrol_tpu peers. We skip only when state is
            # still all-zero — a zero state on the wire is the incast
            # *request* marker (repo.go:78-90).
            cap = int(self.directory.cap_base_nt[ts[0].row])
            if admitted_nt:
                # patrol-audit: the device path's admitted-token booking
                # (the host fast path books in _host_serve_ticket).
                self._audit.note(
                    ts[0].name, admitted_nt, cap, ts[0].rate.per_ns, now_clock
                )
            if own_a[i] or own_t[i] or elapsed[i] or cap:
                broadcasts.append(
                    wire.from_nanotokens(
                        ts[0].name,
                        cap + int(sum_a[i]),
                        int(sum_t[i]),
                        int(elapsed[i]),
                        origin_slot=self.node_slot,
                        cap_nt=cap,
                        lane_added_nt=int(own_a[i]),
                        lane_taken_nt=int(own_t[i]),
                        # A sampled take in the group propagates its trace
                        # id on the state broadcast (the group shares one
                        # packet, so one id rides it).
                        trace_id=next(
                            (t.trace_id for t in ts if t.trace_id), None
                        ),
                    )
                )
        if unpin:
            self.directory.unpin_rows(unpin)
        self._emit_broadcasts(broadcasts)
        return done_ns, drained

    def _apply_merges(self, deltas: DeltaArrays) -> None:
        # Scalar-semantics (reference-peer) deltas go through the
        # deficit-attribution kernel; the common case is none of them.
        # Lane merges apply FIRST: a scalar echo's aggregate already
        # includes peer lanes broadcast before it, so attributing the
        # deficit before those lane deltas land would double-count their
        # grants into the sender's lane — permanently (lanes are monotone).
        # Deficit attribution is monotone-decreasing in other-lane values,
        # so lane-first is always the conservative order.
        scalar_subset = None
        if deltas.scalar.any():
            sc = deltas.scalar
            scalar_subset = DeltaArrays(*(a[sc] for a in deltas))
            if sc.all():
                self._apply_scalar_merges(scalar_subset)
                return
            deltas = DeltaArrays(*(a[~sc] for a in deltas))
        self._apply_lane_merges(deltas)
        if scalar_subset is not None:
            self._apply_scalar_merges(scalar_subset)

    def _apply_lane_merges(self, deltas: DeltaArrays) -> None:
        if not len(deltas):  # a zero-length chunk is a no-op tick
            return
        # Device-commit pipeline: a drain wider than one block's budget
        # (the feeder pulls up to _commit_blocks blocks per tick) folds
        # across ALL its blocks and commits in ONE donated dispatch —
        # every per-block kernel below is shape-capped at MAX_MERGE_ROWS.
        if len(deltas) > MAX_MERGE_ROWS:
            self._commit_coalesced(deltas)
            return
        # Fold on the host (sort by (row, slot), max-join duplicates): the
        # device scatter then asserts unique+sorted indices, measured +28%
        # on v5e where the scatter serializes per update
        # (scripts/probe_scatter.py), and hot rows commit as row windows.
        t0 = time.perf_counter_ns()
        packed, dense = self._fold_hybrid(deltas)
        _obs_stage(hist.STAGE_FOLD, t0, trace_mod.EV_FOLD, len(deltas))
        # Stage the operands on device BEFORE the state lock: the H2D
        # transfer then overlaps the previous tick's compute instead of
        # serializing inside the jit call (device-commit pipeline; the
        # fold buffers are freshly allocated per tick, so jax owns them
        # until the async transfer completes).
        t0 = time.perf_counter_ns()
        dense_dev = (
            tuple(jax.device_put(x) for x in dense) if dense is not None else None
        )
        packed_dev = jax.device_put(packed) if packed is not None else None
        _obs_stage(hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT, len(deltas))
        t0 = time.perf_counter_ns()
        with self._state_mu, _dispatch("merge_folded"):
            if dense_dev is not None:
                self.state = _jit_merge_rows_dense()(self.state, *dense_dev)
            if packed_dev is not None:
                self.state = _jit_merge_folded()(self.state, packed_dev)
        _obs_stage(
            hist.STAGE_DISPATCH, t0, trace_mod.EV_COMMIT_DISPATCH,
            len(deltas),
        )
        self._observe_device_commit("merge_folded", t0, len(deltas))
        self._ticks += 1

    def _commit_coalesced(self, deltas: DeltaArrays) -> None:
        """Device-commit pipeline: fold a multi-block drain ONCE across
        all its blocks and commit it as a single donated fixed-shape
        dispatch (ops/commit.py) instead of one dispatch per block —
        exact because the join is commutative/idempotent (patrol-prove
        PTP002/PTP003 on the registered commit root), so cross-block
        fold order cannot matter. The packed matrix fills a reusable
        staging buffer and ships via ``jax.device_put`` before the state
        lock (transfer overlaps the previous tick's compute); the buffer
        returns to the pool on the completer thread once the transfer is
        ready, which also keeps pipeline depth bounded."""
        blocks_in = -(-len(deltas) // MAX_MERGE_ROWS)  # ceil
        t0 = time.perf_counter_ns()
        ur, us, ua, ut, er, e = self._fold_core(deltas)
        _obs_stage(hist.STAGE_FOLD, t0, trace_mod.EV_FOLD, len(deltas))
        if len(ur) <= MAX_MERGE_ROWS:
            # The fold collapsed the drain into one block (hot keys /
            # cross-block duplicates): the single-block folded kernel is
            # the cheaper dispatch, and the coalescing already happened
            # on host.
            packed = self._pack_folded(ur, us, ua, ut, er, e)
            t0 = time.perf_counter_ns()
            packed_dev = jax.device_put(packed)
            _obs_stage(hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT, len(ur))
            t0 = time.perf_counter_ns()
            with self._state_mu, _dispatch("merge_folded"):
                self.state = _jit_merge_folded()(
                    self.state, packed_dev
                )
            _obs_stage(
                hist.STAGE_DISPATCH, t0, trace_mod.EV_COMMIT_DISPATCH,
                len(ur),
            )
            self._observe_device_commit("merge_folded", t0, len(ur))
        else:
            shape = commit_mod.commit_shape(len(ur), MAX_MERGE_ROWS)
            buf = self._staging.lease(shape)
            commit_mod.pack_commit_blocks(
                ur, us, ua, ut, er, e, MAX_MERGE_ROWS, out=buf
            )
            t0 = time.perf_counter_ns()
            dev = jax.device_put(buf)
            _obs_stage(hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT, len(ur))
            t0 = time.perf_counter_ns()
            with self._state_mu, _dispatch("commit_blocks"):
                self.state = _jit_commit_packed()(self.state, dev)
            _obs_stage(
                hist.STAGE_DISPATCH, t0, trace_mod.EV_COMMIT_DISPATCH,
                len(ur),
            )
            self._observe_device_commit("commit_blocks", t0, len(ur))
            self._release_when_shipped(dev, buf)
        self._ticks += 1
        profiling.COUNTERS.inc("commit_blocks_coalesced", blocks_in)
        profiling.COUNTERS.inc("commit_dispatches")

    def _device_marker(self):
        """A tiny device value depending on the just-dispatched state —
        ``block_until_ready`` on it observes the kernel's completion
        without touching the (donation-chained) state buffers themselves:
        the marker is a fresh output, so later ticks donating the state
        away can never invalidate it."""
        try:
            return self.state.elapsed[:1]
        except Exception:  # pragma: no cover - observability only
            return None

    def _observe_device_commit(
        self, kernel: str, t_dispatch_ns: int, n: int, marker=None
    ) -> None:
        """patrol-fleet device-dispatch timing: ride the completion
        pipeline to record this commit dispatch's device-side
        dispatch→ready duration into the ``device_commit_ns`` stage
        histogram and the per-kernel histogram. The wait runs on the
        completer thread (which blocks on device results anyway);
        dispatch-ahead keeps the feeder unblocked.

        ``marker`` lets a caller supply a fresh output of the observed
        program itself. MeshEngine must: the default ``_device_marker``
        slice is a NEW program over the sharded state, and launching it
        outside the state mutex races whatever collective another thread
        dispatches under it (host-platform device pools interleave the
        two rendezvous and deadlock)."""
        if not DEVICE_TIMING:
            return
        if marker is None:
            marker = self._device_marker()
        if marker is None:
            return
        kh = hist.kernel_histogram(kernel)

        def done() -> None:
            # Device-commit latency gauge: awaiting the marker IS the
            # measurement. Runs on the completer, never the feeder.
            jax.block_until_ready(marker)  # patrol-lint: disable=PTD003
            dur = time.perf_counter_ns() - t_dispatch_ns
            hist.STAGE_DEVICE_COMMIT.record(dur)
            kh.record(dur)
            # Adaptive commit sizing input: per-row device-commit cost
            # EWMA (completer writes, feeder reads — a racy float gauge
            # by design; a stale read only mis-sizes one tick's drain).
            per_row = dur / max(n, 1)
            prev = self._commit_row_ns_ewma
            self._commit_row_ns_ewma = (
                per_row if prev == 0.0 else 0.8 * prev + 0.2 * per_row
            )
            tr = trace_mod.TRACE
            if tr.enabled:
                tr.record(trace_mod.EV_DEVICE_READY, dur, n)

        self._enqueue_completion(done, (), {})

    def _release_when_shipped(self, dev, buf: np.ndarray) -> None:
        """Queue a transfer completion: return the staging buffer to the
        pool once the shipped operand is READY on device (device_put
        copies — it never aliases the host source — so readiness means
        the host bytes are free to refill). Rides the completion
        pipeline, so the feeder keeps dispatching ahead while the
        completer waits out the transfer."""

        def done() -> None:
            # Staging-recycle gate on the completion pipeline (see
            # docstring): the wait rides the completer by construction.
            jax.block_until_ready(dev)  # patrol-lint: disable=PTD003
            self._staging.release(buf)

        self._enqueue_completion(done, (), {})

    @staticmethod
    def _fold_lane_merges(deltas: DeltaArrays) -> np.ndarray:
        """Tick-level CRDT fold: sort by (row, slot), max-join duplicate
        keys, and fold the elapsed updates per ROW — the preparation that
        lets the device scatter assert unique+sorted indices (measured
        +28% on v5e, where scatter serializes per update; and a hot-key
        tick shrinks to its unique-key count before padding). Folding is
        exactly the join the kernel computes, so order never matters.

        Padding appends out-of-bounds SENTINEL keys (row ``_FOLD_PAD_ROW``
        far above any bucket row, distinct slot/row per entry) that the
        scatter's ``mode="drop"`` discards — every index the kernel sees
        is genuinely unique and sorted, so the asserted scatter flags are
        literally true rather than resting on duplicate-index behavior.
        A zero-length tick folds to an all-sentinel (no-op) matrix —
        reachable only by direct callers (tests): the engine's tick loop
        early-returns on empty chunks before folding.
        Returns the packed int64[6, k] tick matrix:
        rows, slots, added, taken, erows, elapsed."""
        if not len(deltas):
            k = _pad_size(0)
            packed = np.zeros((6, k), dtype=np.int64)
            packed[0] = _FOLD_PAD_ROW
            packed[1] = np.arange(k)
            packed[4] = _FOLD_PAD_ROW + np.arange(k)
            return packed
        return DeviceEngine._pack_folded(*DeviceEngine._fold_core(deltas))

    @staticmethod
    def _fold_core(deltas: DeltaArrays):
        """The fold computation: → (unique-pair rows, slots, added, taken,
        per-unique-row rows, elapsed), all sorted, duplicates max-joined."""
        order = np.lexsort((deltas.slots, deltas.rows))
        r = deltas.rows[order]
        s = deltas.slots[order]
        new_key = np.empty(len(r), bool)
        new_key[0] = True
        np.logical_or(r[1:] != r[:-1], s[1:] != s[:-1], out=new_key[1:])
        starts = np.flatnonzero(new_key)
        a = np.maximum.reduceat(deltas.added_nt[order], starts)
        t = np.maximum.reduceat(deltas.taken_nt[order], starts)
        el_sorted = deltas.elapsed_ns[order]
        new_row = np.empty(len(r), bool)
        new_row[0] = True
        np.not_equal(r[1:], r[:-1], out=new_row[1:])
        row_starts = np.flatnonzero(new_row)
        er = r[row_starts]
        e = np.maximum.reduceat(el_sorted, row_starts)
        return r[starts], s[starts], a, t, er, e

    @staticmethod
    def _pack_folded(ur, us, ua, ut, er, e) -> Optional[np.ndarray]:
        """Sentinel-padded int64[6, k] tick matrix from folded arrays
        (None when empty). Sentinel tail: rows above every live row keep
        the keys sorted; distinct slots keep them unique; mode="drop"
        discards them."""
        n = len(ur)
        if n == 0:
            return None
        ne = len(er)
        k = _pad_size(n)
        packed = np.empty((6, k), dtype=np.int64)
        packed[0, :n] = ur
        packed[1, :n] = us
        packed[2, :n] = ua
        packed[3, :n] = ut
        packed[0, n:] = _FOLD_PAD_ROW
        packed[1, n:] = np.arange(k - n)
        packed[2, n:] = 0
        packed[3, n:] = 0
        packed[4, :ne] = er
        packed[5, :ne] = e
        packed[4, ne:] = _FOLD_PAD_ROW + np.arange(k - ne)
        packed[5, ne:] = 0
        return packed

    def _fold_hybrid(self, deltas: DeltaArrays):
        return fold_hybrid(deltas, self.config.nodes, self._row_dense_min)

    def _apply_scalar_merges(self, deltas: DeltaArrays) -> None:
        """Deficit-attribution merge of reference-peer deltas (interop).
        Chunks batches past the padded-shape cap — _pad_size clamps at
        MAX_MERGE_ROWS, so a bigger batch would otherwise overflow its
        packed matrix and fail the whole tick."""
        t0 = time.perf_counter_ns()
        for lo in range(0, len(deltas), MAX_MERGE_ROWS):
            chunk = DeltaArrays(*(a[lo : lo + MAX_MERGE_ROWS] for a in deltas))
            n = len(chunk)
            k = _pad_size(n)
            packed = np.zeros((5, k), dtype=np.int64)
            packed[0, :n] = chunk.rows
            packed[1, :n] = chunk.slots
            packed[2, :n] = chunk.added_nt
            packed[3, :n] = chunk.taken_nt
            packed[4, :n] = chunk.elapsed_ns
            with self._state_mu, _dispatch("merge_scalar"):
                self.state = _jit_merge_scalar_packed()(
                    self.state, jnp.asarray(packed)
                )
            self._ticks += 1
        self._observe_device_commit("merge_scalar", t0, len(deltas))

    @staticmethod
    def _note_take_coalesce(keys, groups) -> None:
        """Hot-key coalescing receipt for one tick's take pack (shared
        with the mesh fused path): rows dispatched, rows dispatched as
        take-n (nreq > 1), with the flight-recorder arg carrying how many
        tickets rode beyond one-per-row."""
        profiling.COUNTERS.inc("take_rows_dispatched", len(keys))
        multi = sum(1 for key in keys if len(groups[key]) > 1)
        if multi:
            profiling.COUNTERS.inc("take_rows_coalesced", multi)
            tr = trace_mod.TRACE
            if tr.enabled:
                tr.record(
                    trace_mod.EV_TAKE_COALESCE,
                    0,
                    sum(len(groups[key]) for key in keys) - len(keys),
                )

    def _apply_takes(self, tickets: Sequence[TakeTicket]) -> None:
        keys, groups = self._group_tickets(tickets)
        self._note_take_coalesce(keys, groups)
        k = _pad_size(len(keys), hi=MAX_TAKE_ROWS)
        packed = self._staging.lease((TAKE_PACK_ROWS, k))
        packed[:] = 0  # padding rows must stay nreq=0 no-ops
        for i, key in enumerate(keys):
            ts = groups[key]
            first = ts[0]
            packed[0, i] = first.row
            # Earliest arrival clock for the group: conservative (refills
            # least); exact when callers share an injected clock tick.
            packed[1, i] = min(t.now_ns for t in ts)
            packed[2, i] = first.rate.freq
            packed[3, i] = first.rate.per_ns
            packed[4, i] = first.count * NANO
            packed[5, i] = len(ts)
            packed[6, i] = self.directory.cap_base_nt[first.row]
            packed[7, i] = self.directory.created_ns[first.row]

        t0 = time.perf_counter_ns()
        packed_dev = jax.device_put(packed)  # staged ahead of the lock
        _obs_stage(hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT, len(keys))
        # The take stages' stamps: the tick's drain (every ticket drained
        # here for the first time carries it), the state lock asked for
        # (t_ask) and held (t_locked), the dispatch returned (t_ret).
        t_drain = max(t.drain_ns for t in tickets)
        t_ask = time.perf_counter_ns()
        with self._state_mu:
            t_locked = time.perf_counter_ns()
            with _dispatch("take_packed"):
                self.state, out = _jit_take_packed(self.node_slot)(
                    self.state, packed_dev
                )
            t_ret = time.perf_counter_ns()
        _obs_stage(
            hist.STAGE_DISPATCH, t_ask, trace_mod.EV_COMMIT_DISPATCH, len(keys)
        )
        self._ticks += 1
        n_keys = len(keys)

        def complete() -> None:
            t_thunk = time.perf_counter_ns()
            # THE sanctioned completer readback: one batched D2H per
            # take tick, on the completion pipeline by construction.
            res = np.asarray(out)  # patrol-lint: disable=PTD003
            if DEVICE_TIMING:
                # Device-side take duration: dispatch → results readable
                # (the completion-pipeline readback delta, patrol-fleet).
                dur = time.perf_counter_ns() - t_ask
                hist.STAGE_DEVICE_TAKE.record(dur)
                hist.kernel_histogram("take_packed").record(dur)
                tr = trace_mod.TRACE
                if tr.enabled:
                    tr.record(trace_mod.EV_DEVICE_READY, dur, n_keys)
            # Device done ⇒ the staged request matrix is consumed on any
            # backend: recycle it.
            self._staging.release(packed)
            have, admitted, own_a, own_t, elapsed, sum_a, sum_t = res
            done_ns, drained = self._complete_groups(
                keys, groups, have, admitted, own_a, own_t, elapsed, sum_a, sum_t
            )
            if drained:
                _record_take_stages(
                    drained, t_drain, t_ask, t_locked, t_ret, t_thunk, done_ns
                )

        self._enqueue_completion(complete, keys, groups)
