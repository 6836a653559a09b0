"""MeshEngine: the device engine over a multi-device mesh.

Same public surface and host protocol behavior as
:class:`patrol_tpu.runtime.engine.DeviceEngine`, but state lives sharded
over a ``(replicas × shards)`` ``jax.sharding.Mesh``
(:mod:`patrol_tpu.parallel.topology`): bucket rows partition across the
``"b"`` axis, full replicas along ``"r"`` ingest disjoint slices of each
tick's work and converge with a hierarchical tree max-reduce — the
intra-slice analogue of the reference's UDP broadcast (repo.go:123-158),
riding ICI as log2(R) ppermute rounds instead of a flat all-gather
(topology._tree_allreduce_max; Tascade's coalescing-reduction shape).

Each tick fuses merge + take + converge into ONE shard_map'd device call;
the host router places every take in its row's home (replica, shard) block
(single-writer lanes ⇒ exact convergence) and spreads merges round-robin.

Pod-scale serving pipeline (this file's PR): the tick plumbing is the
single-device device-commit pipeline inherited intact —

* the feeder drains up to ``_commit_blocks`` × MAX_MERGE_ROWS deltas per
  tick (no more opting down to one block) and FOLDS the whole drain once
  on host (``DeviceEngine._fold_core``), so cross-block duplicate
  (row, slot) keys coalesce before any routing;
* the routed take/merge matrices fill reusable :class:`StagingPool`
  buffers and ship via ``jax.device_put`` with the mesh sharding BEFORE
  the state lock, so the H2D transfer overlaps the previous tick's
  compute; buffers recycle on the completer once the transfer is ready;
* completions ride the inherited dispatch-ahead pipeline
  (``DISPATCH_AHEAD`` deep), so result readback + ticket fanout overlap
  the next tick's device compute;
* a drain whose densest (replica, shard) block would pad past the warmed
  ``MESH_WARM_MAX`` diagonal splits into sub-dispatches on the ACTUAL
  per-block fill (not the total count): all merge chunks dispatch first,
  then take chunks (the last merge chunk shares a dispatch with the
  first take chunk) — bit-exact versus an unsplit tick, because merges
  are idempotent joins, every take key rides exactly one chunk after
  every merge landed, and take rows are unique per tick.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from patrol_tpu.models.limiter import NANO, LimiterConfig
from patrol_tpu.ops.merge import read_rows
from patrol_tpu.parallel import topology as topo
from patrol_tpu.runtime.bucket import ClockFn, system_clock
from patrol_tpu.runtime import engine as engine_mod
from patrol_tpu.runtime.engine import (
    MAX_MERGE_ROWS,
    BroadcastFn,
    DeltaArrays,
    DeviceEngine,
    TakeTicket,
    _dispatch,
    _jit_merge_scalar_packed,
    _obs_stage,
    _pad_size,
)
from patrol_tpu.utils import histogram as hist
from patrol_tpu.utils import profiling
from patrol_tpu.utils import trace as trace_mod

log = logging.getLogger("patrol.mesh")


# The largest (diagonal) block size warmup() pre-compiles AND the hard cap
# on any runtime dispatch's padded block size. _apply splits a tick whose
# densest (replica, shard) block would exceed this into sequential
# sub-dispatches instead of padding past the warmed set — merges are
# idempotent CRDT joins applied before every take chunk and each take key
# rides exactly one chunk, so the split is bit-exact versus an unsplit
# tick, and no reachable tick shape can JIT a fresh variant mid-serve (a
# multi-second p99 spike on a remote-compile TPU). Scope: the fused
# merge+take+converge step AND (since this PR) the scalar-interop kernel
# — warmup() pre-compiles _jit_merge_scalar_packed's pad diagonal too, so
# a first reference-peer batch no longer compiles lazily mid-serve.
MESH_WARM_MAX = 1 << 12


class _HostSyncStateLock(profiling.ProfiledLock):
    """State mutex for HOST-PLATFORM meshes: materializes the in-flight
    device program before every release. XLA's forced host "devices"
    (``--xla_force_host_platform_device_count``) execute on one shared
    thread pool with no per-device stream FIFO, so two concurrently
    in-flight collective programs can interleave their rendezvous across
    the pool and deadlock — endless ``participant ... may be stuck``
    spins, first hit by the churn gate's incast snapshot gathers racing
    the fused step on an 8-device mesh. Holding every dispatch to
    completion inside the state lock keeps at most ONE collective
    program in flight; real accelerators have proper per-device streams
    and keep the plain lock (async dispatch-ahead intact)."""

    __slots__ = ("_engine",)

    def __init__(self, name: str, engine: "MeshEngine"):
        super().__init__(name)
        self._engine = engine

    def release(self) -> None:
        st = getattr(self._engine, "state", None)
        if st is not None:
            try:
                jax.block_until_ready(st.pn)
            except Exception:  # a poisoned dispatch must still unlock
                pass
        super().release()

    def __exit__(self, *exc) -> None:
        self.release()


class MeshEngine(DeviceEngine):
    # Idle demotion stays off here — DOCUMENTED AND GATED, not silent:
    # the per-row gather/zero pair would run against SHARDED planes,
    # where each demotion's resharding (gather across "b", zero scatter
    # back) costs a cross-device round per window — unmeasured, and the
    # sharded zero_rows would reshard the gathered block through host
    # memory. stats()/bench receipts carry
    # ``mesh_demotion: unsupported`` so the Zipf-lifecycle work (ROADMAP
    # item 4) sees the constraint machine-readably instead of finding a
    # silently-disabled flag.
    _demotion_capable = False

    # NOTE: _commit_blocks is INHERITED (PATROL_COMMIT_BLOCKS, default 4)
    # since the pod-scale PR — the fused step's host router folds and
    # splits per block itself, so a multi-block drain coalesces into the
    # fewest dispatches the warmed diagonal allows (previously this class
    # opted down to 1 block per tick and left the device idle between
    # short ticks). The r15 ``auto`` sizing stays OFF here: the fused
    # step's per-block routing economics are unmeasured under a moving
    # drain width, so this class pins the static default.
    _commit_blocks_auto = False
    # Raw-plane device ingest (ops/ingest.py) opts out too: a
    # decode_fold_raw dispatch against the SHARDED planes would reshard
    # the scatter through host memory — unmeasured; the delta plane
    # takes the python decode path.
    _raw_ingest_capable = False
    # The rx-thread interval fold opts out for the same reason the raw
    # ingest does, plus a liveness one: delta_fold against SHARDED
    # planes is a collective program, and dispatching it from the rx
    # context holds the state mutex across a mesh rendezvous — racing
    # the feeder's own collective step (a deadlock on host-platform
    # device pools, which have no per-device stream FIFO). Decoded
    # intervals route through the queued classify path and merge inside
    # the fused tick instead.
    _interval_fold_capable = False

    def __init__(
        self,
        config: LimiterConfig,
        replicas: int = 1,
        node_slot: int = 0,
        clock: ClockFn = system_clock,
        on_broadcast: Optional[BroadcastFn] = None,
        devices=None,
    ):
        self.mesh = topo.make_mesh(replicas=replicas, devices=devices)
        shards = self.mesh.shape[topo.BUCKET_AXIS]
        if config.buckets % shards:
            raise ValueError(
                f"buckets ({config.buckets}) must divide over {shards} shards"
            )
        # The state is made sharded: zeros materialized on one device
        # first would not fit it at 4M x 256.
        super().__init__(
            config, node_slot=node_slot, clock=clock, on_broadcast=on_broadcast,
            device=topo.state_sharding(self.mesh),
        )
        # Host-platform collective safety (_HostSyncStateLock): swap the
        # state mutex BEFORE the first collective dispatch. Nothing
        # touches state concurrently this early — no bucket exists for
        # the feeder/lifecycle threads to reach.
        if next(iter(self.mesh.devices.flat)).platform == "cpu":
            self._state_mu = _HostSyncStateLock("engine.state", self)
        self._shard_bytes = self._held_bytes()
        # Host-side mesh tick accounting, read by stats() from API
        # threads while the feeder mutates it — its own lock (leaf-only:
        # never held together with the engine's shared locks), registered
        # in analysis/race.py::GUARDS like every other shared attribute.
        self._mesh_mu = threading.Lock()
        # Serializes resize() calls (admin-driven, rare); never held
        # together with _cond/_state_mu acquisition ordering conflicts —
        # resize takes _resize_mu → _cond → _state_mu, and no other path
        # takes _resize_mu at all.
        self._resize_mu = threading.Lock()
        self._mesh_metrics: Dict[str, int] = {
            "mesh_fused_dispatches": 0,
            "mesh_split_ticks": 0,
            "mesh_sub_dispatches": 0,
            "mesh_routed_takes": 0,
            "mesh_routed_deltas": 0,
            "mesh_folded_dupes": 0,
        }
        try:
            self.plan = topo.plan_for(self.mesh, config)
            self._step = topo.build_cluster_step_packed(self.mesh, node_slot)
            self._mat_sharding = topo.batch_sharding(self.mesh)
        except BaseException:
            # The base engine is live (threads + native directory handle);
            # a half-built MeshEngine must release them or every later
            # engine in the process inherits a shrunken handle registry.
            self.stop()
            raise

    # -- elasticity ---------------------------------------------------------

    def resize(
        self,
        replicas: int = 1,
        devices=None,
        timeout: float = 30.0,
    ) -> dict:
        """Live mesh resharding (patrol-membership, ROADMAP 3c): grow or
        shrink the device mesh WITHOUT restarting the engine or losing a
        single queued take.

        Protocol — quiesce, swap, resume:

        1. **Pause** the feeder between ticks (``_tick_paused`` under
           ``_cond``): work queues keep absorbing submissions — /take
           callers just see one tick's extra latency — but nothing new
           dispatches.
        2. **Wait** for the in-flight tick (``_busy``) to clear. Pending
           completions need no wait: their device results are already
           materialized arrays, indifferent to where state lives next.
        3. **Swap** under ``_state_mu``: build the new mesh's plan, fused
           step, and matrix sharding, then ``device_put`` the state under
           the new :class:`~jax.sharding.NamedSharding` — a straight
           cross-sharding transfer, no recompile dance and no host
           round-trip of the planes. State is a join-semilattice, and the
           transfer is a bit-exact relayout: per-bucket digests before
           and after are identical (the churn bench gates on this).
        4. **Resume** the feeder; the next tick routes against the new
           plan and JITs the new step's first shapes (call
           :meth:`warmup` after, if p99 matters more than the pause).

        Validates ``buckets %% shards == 0`` BEFORE pausing, so an
        invalid target never stalls serving. Returns a receipt dict.
        """
        new_mesh = topo.make_mesh(replicas=replicas, devices=devices)
        shards = new_mesh.shape[topo.BUCKET_AXIS]
        if self.config.buckets % shards:
            raise ValueError(
                f"buckets ({self.config.buckets}) must divide over "
                f"{shards} shards"
            )
        with self._resize_mu:
            old_shape = (self.plan.replicas, self.plan.shards)
            with self._cond:
                self._tick_paused = True
            try:
                # In-flight tick drains; _busy flips under _cond, and with
                # the pause already visible the feeder cannot start another.
                deadline = time.monotonic() + timeout
                while True:
                    with self._cond:
                        if not self._busy:
                            break
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            "resize quiesce timed out waiting for the "
                            "in-flight tick"
                        )
                    time.sleep(0.0005)
                plan = topo.plan_for(new_mesh, self.config)
                step = topo.build_cluster_step_packed(new_mesh, self.node_slot)
                sharding = topo.batch_sharding(new_mesh)
                with self._state_mu:
                    self.state = topo.place_state(self.state, new_mesh)
                    self._state_gen += 1  # scrape-mirror epoch: new placement
                    self._shard_bytes = self._held_bytes()
                    self.mesh = new_mesh
                    self.plan = plan
                    self._step = step
                    self._mat_sharding = sharding
            finally:
                with self._cond:
                    self._tick_paused = False
                    self._cond.notify_all()
        from patrol_tpu.utils import profiling

        profiling.COUNTERS.inc("mesh_resizes")
        receipt = {
            "from": {"replicas": old_shape[0], "shards": old_shape[1]},
            "to": {"replicas": plan.replicas, "shards": plan.shards},
            "devices": len(new_mesh.devices.flatten()),
        }
        log.info("mesh resized", extra=receipt)
        return receipt

    # -- tick ---------------------------------------------------------------

    def _apply(
        self, deltas: Optional[DeltaArrays], tickets: Sequence[TakeTicket]
    ) -> None:
        # Scalar-semantics (reference-peer) deltas can't ride the fused lane
        # merge: they need deficit attribution against the whole row. Rare
        # interop path — peel them into the base kernel (GSPMD shards it),
        # applied AFTER the fused step: lane merges land first so a scalar
        # echo's aggregate (which already includes peer lanes broadcast
        # before it) isn't double-attributed to the sender's lane.
        scalar_subset = None
        if deltas is not None and deltas.scalar.any():
            sc = deltas.scalar
            scalar_subset = DeltaArrays(*(a[sc] for a in deltas))
            deltas = DeltaArrays(*(a[~sc] for a in deltas)) if not sc.all() else None

        keys, groups = self._group_tickets(tickets) if tickets else ([], {})
        if keys:
            self._note_take_coalesce(keys, groups)
        try:
            self._apply_fused(deltas, keys, groups)
        finally:
            if scalar_subset is not None:
                self._apply_scalar_merges(scalar_subset)

    def _device_marker(self):
        # Never slice the sharded state into a fresh marker program: any
        # caller without an explicit marker would dispatch it OUTSIDE the
        # state mutex and interleave its collective rendezvous with a
        # concurrently-locked gather (see _observe_device_commit). Mesh
        # dispatch sites pass their own program output as the marker.
        return None

    def _apply_fused(
        self,
        deltas: Optional[DeltaArrays],
        keys: List,
        groups: Dict,
    ) -> None:
        """The fused mesh tick: fold the whole (multi-block) drain once,
        route per (replica, shard) block, dispatch the fewest
        ≤MESH_WARM_MAX-padded fused steps that cover it — merge chunks
        strictly before take chunks (sharing the boundary dispatch), so
        the result is bit-exact versus one unsplit dispatch."""
        plan = self.plan
        W = MESH_WARM_MAX

        # -- fold: the coalesced-commit analogue (device-commit pipeline).
        # Cross-block duplicate (row, slot) keys max-join on host; the
        # per-row elapsed fold rides the row's FIRST pair (zeros
        # elsewhere join as no-ops).
        folded = None
        blk_m = msub = None
        m = 0
        raw_n = len(deltas) if deltas is not None else 0
        if raw_n:
            t0 = time.perf_counter_ns()
            ur, us, ua, ut, er, e = DeviceEngine._fold_core(deltas)
            first = np.flatnonzero(
                np.concatenate(([True], ur[1:] != ur[:-1]))
            )
            el = np.zeros(len(ur), np.int64)
            el[first] = e
            folded = (ur, us, ua, ut, el)
            _obs_stage(hist.STAGE_FOLD, t0, trace_mod.EV_FOLD, raw_n)
            # Block assignment + within-block rank → sub-dispatch index.
            blk_m = topo.delta_block_assignment(plan, ur)
            counts = np.bincount(blk_m, minlength=plan.blocks)
            order = np.argsort(blk_m, kind="stable")
            run_start = np.concatenate(([0], np.cumsum(counts)))[blk_m[order]]
            rank = np.empty(len(ur), np.int64)
            rank[order] = np.arange(len(ur), dtype=np.int64) - run_start
            msub = rank // W
            m = int(msub.max()) + 1

        # -- take placement: per-block arrival rank → (chunk, slot).
        key_sub: List[int] = []
        fill_t = [0] * plan.blocks
        for key in keys:
            replica, shard, _local = plan.locate(key[0])
            blk = plan.block_index(replica, shard)
            key_sub.append(fill_t[blk] // W)
            fill_t[blk] += 1
        t = (max(key_sub) + 1) if keys else 0

        n_dispatch = m + t - (1 if m and t else 0)
        if n_dispatch == 0:
            return
        if n_dispatch > 1:
            log.debug(
                "mesh tick split into %d sub-dispatches (%d merge chunks, "
                "%d take chunks)",
                n_dispatch, m, t,
            )

        take_base = (m - 1) if m else 0  # dispatch index of take chunk 0
        failed = False
        for d in range(n_dispatch):
            mi = d if d < m else None
            ti = d - take_base if (t and d >= take_base) else None
            keys_d = (
                [k for j, k in enumerate(keys) if key_sub[j] == ti]
                if ti is not None
                else []
            )
            try:
                self._dispatch_fused(folded, blk_m, msub, mi, keys_d, groups)
            except Exception:
                # Partial-failure discipline: earlier sub-dispatches
                # already admitted takes and debited tokens on device —
                # their queued completions must stand. Fail ONLY the
                # tickets of this and later chunks, and swallow
                # (re-raising would make the tick loop's catch-all race
                # those live completions with blanket failures).
                log.exception(
                    "mesh sub-dispatch %d/%d failed; failing undispatched "
                    "takes only",
                    d + 1,
                    n_dispatch,
                )
                profiling.COUNTERS.inc("engine_tick_failures")
                later = [
                    tk
                    for j, key in enumerate(keys)
                    if ti is None or key_sub[j] >= ti
                    for tk in groups[key]
                ]
                self._fail_tickets(later)
                failed = True
                break

        n_pairs = len(folded[0]) if folded is not None else 0
        with self._mesh_mu:
            mm = self._mesh_metrics
            mm["mesh_fused_dispatches"] += n_dispatch
            if n_dispatch > 1 and not failed:
                mm["mesh_split_ticks"] += 1
                mm["mesh_sub_dispatches"] += n_dispatch
            mm["mesh_routed_takes"] += len(keys)
            mm["mesh_routed_deltas"] += n_pairs
            mm["mesh_folded_dupes"] += raw_n - n_pairs

    def _dispatch_fused(
        self,
        folded,
        blk_m: Optional[np.ndarray],
        msub: Optional[np.ndarray],
        mi: Optional[int],
        keys_d: List,
        groups: Dict,
    ) -> None:
        """One fused device dispatch: the selected merge chunk + take
        chunk, square-padded to the warmed diagonal, staged through the
        pool and shipped sharded before the state lock."""
        plan = self.plan

        deltas_d = None
        blk_d = None
        max_fill_m = 0
        if mi is not None:
            sel = msub == mi
            deltas_d = tuple(a[sel] for a in folded)
            blk_d = blk_m[sel]
            max_fill_m = int(
                np.bincount(blk_d, minlength=plan.blocks).max(initial=0)
            )

        takes_d = []
        max_fill_t = 0
        if keys_d:
            fill = [0] * plan.blocks
            for key in keys_d:
                ts = groups[key]
                first = ts[0]
                replica, shard, _local = plan.locate(first.row)
                blk = plan.block_index(replica, shard)
                fill[blk] += 1
                takes_d.append(
                    (
                        first.row,
                        min(tk.now_ns for tk in ts),
                        first.rate.freq,
                        first.rate.per_ns,
                        first.count * NANO,
                        len(ts),
                        int(self.directory.cap_base_nt[first.row]),
                        int(self.directory.created_ns[first.row]),
                    )
                )
            max_fill_t = max(fill)

        # Square the paddings: only DIAGONAL (k, k) shapes ever compile,
        # so warmup's size sweep covers every runtime dispatch — an
        # off-diagonal pair would JIT a fresh variant mid-serve (a
        # multi-second p99 spike on a remote-compile TPU). Padded entries
        # are no-ops, so the cost is a slightly wider batch.
        k = _pad_size(max(max_fill_m, max_fill_t, 1), lo=8, hi=MESH_WARM_MAX)

        take_buf = self._staging.lease((topo.TAKE_MAT_ROWS, plan.blocks * k))
        merge_buf = self._staging.lease((topo.MERGE_MAT_ROWS, plan.blocks * k))
        _tm, _mm, placed = topo.route_packed(
            plan, takes_d, deltas_d, k, k,
            take_out=take_buf, merge_out=merge_buf, delta_blocks=blk_d,
        )
        # Stage both matrices on device (sharded) BEFORE the state lock:
        # the H2D transfer overlaps the previous dispatch's compute, and
        # device_put copies — the staging buffers recycle once the
        # transfer is ready, on the completer.
        t0 = time.perf_counter_ns()
        take_dev = jax.device_put(take_buf, self._mat_sharding)
        merge_dev = jax.device_put(merge_buf, self._mat_sharding)
        _obs_stage(
            hist.STAGE_H2D, t0, trace_mod.EV_H2D_PUT,
            len(takes_d) + (len(deltas_d[0]) if deltas_d else 0),
        )
        t0 = time.perf_counter_ns()
        with self._state_mu, _dispatch("mesh_step"):
            self.state, out = self._step(self.state, take_dev, merge_dev)
        _obs_stage(
            hist.STAGE_DISPATCH, t0, trace_mod.EV_COMMIT_DISPATCH,
            len(takes_d),
        )
        self._ticks += 1
        t_dispatch = t0
        self._release_when_shipped(take_dev, take_buf)
        self._release_when_shipped(merge_dev, merge_buf)

        if not keys_d:
            # Merge-only dispatch: device timing rides the completion
            # pipeline. The marker is the step's OWN fresh output — never
            # the default _device_marker slice, which would launch a new
            # collective over the sharded state outside the state mutex
            # and interleave with a concurrently-locked gather.
            self._observe_device_commit(
                "mesh_step", t_dispatch,
                len(deltas_d[0]) if deltas_d else 0,
                marker=out,
            )
            return

        groups_d = {key: groups[key] for key in keys_d}
        n_keys = len(keys_d)

        def complete() -> None:
            # THE sanctioned mesh completer readback: one batched D2H
            # per fused step, on the completion pipeline by construction.
            res = np.asarray(out)  # patrol-lint: disable=PTD003
            if engine_mod.DEVICE_TIMING:
                dur = time.perf_counter_ns() - t_dispatch
                hist.STAGE_DEVICE_TAKE.record(dur)
                hist.kernel_histogram("mesh_step").record(dur)
                tr = trace_mod.TRACE
                if tr.enabled:
                    tr.record(trace_mod.EV_DEVICE_READY, dur, n_keys)
            at = [blk * k + slot for blk, slot in placed]
            self._complete_groups(
                keys_d,
                groups_d,
                res[0][at],
                res[1][at],
                res[2][at],
                res[3][at],
                res[4][at],
                res[5][at],
                res[6][at],
            )

        self._enqueue_completion(complete, keys_d, groups_d)

    def _warm_calls(self) -> list:
        """The fused step at each padded block size — the full diagonal
        through MESH_WARM_MAX, which _apply never exceeds (denser ticks
        split into sub-dispatches; the promotion drain rides it too) —
        plus the SCALAR-INTEROP diagonal (the deficit-attribution
        kernel otherwise compiles on its first reference-peer batch per
        pad size, a multi-second p99 spike), and the introspection
        gathers. After warmup() runs these, no reachable serve-path shape
        compiles mid-serve."""
        calls = []
        blocks = self.plan.blocks
        size = 8
        while size <= MESH_WARM_MAX:
            tb = np.zeros((topo.TAKE_MAT_ROWS, blocks * size), np.int64)
            mb = np.zeros((topo.MERGE_MAT_ROWS, blocks * size), np.int64)
            calls.append((
                self._step,
                (jax.device_put(tb, self._mat_sharding),
                 jax.device_put(mb, self._mat_sharding)),
                0,
            ))
            size <<= 1
        # The scalar-interop kernel chunks batches of up to
        # MAX_MERGE_ROWS entries and pads each to a power of two (all-zero
        # batches: deficit attribution of zero against non-negative lanes
        # is a no-op, so warmed state is untouched).
        hi = _pad_size(MAX_MERGE_ROWS)
        scalar = _jit_merge_scalar_packed()
        size = 8
        while size <= hi:
            calls.append((scalar, (jnp.zeros((5, size), jnp.int64),), "all"))
            size <<= 1
        size = 1
        while size <= 1024:
            calls.append((read_rows, (jnp.zeros(size, jnp.int32),), None))
            size <<= 1
        return calls

    def _held_bytes(self) -> Dict[str, int]:
        """What each device holds of the state: the bytes of its
        addressable shards, so a placement that leaves a device empty (or
        piles the state on one) shows. Fixed once the state is placed:
        taken at construction and on resize."""
        held: Dict[str, int] = {}
        for plane in (self.state.pn, self.state.elapsed):
            for sh in plane.addressable_shards:
                key = str(sh.device.id)
                held[key] = held.get(key, 0) + sh.data.nbytes
        return held

    def stats(self) -> Dict[str, object]:
        with self._mesh_mu:
            out: Dict[str, object] = dict(self._mesh_metrics)
        out["mesh_shard_bytes"] = dict(self._shard_bytes)
        out.update(
            mesh_replicas=self.plan.replicas,
            mesh_shards=self.plan.shards,
            mesh_commit_blocks=self._commit_blocks,
            mesh_warm_max=MESH_WARM_MAX,
            # Machine-readable residency constraint (see _demotion_capable
            # note): consumed by bench --mesh receipts and the ROADMAP
            # item-4 lifecycle work.
            mesh_demotion="unsupported",
            # Bucket lifecycle on the mesh: sharded-plane idle DEMOTION
            # stays unsupported (above), but the lifecycle GC path is
            # fully inherited — the IsZero probe and zero_rows both run
            # as GSPMD programs over the sharded planes, so the mesh
            # sheds cold state via host-directory GC like the
            # single-device engine. Measured cost rides the shared
            # ``gc_sweep_ns`` histogram; reclaim counts ride
            # ``engine_gc_reclaimed`` / ``gc_buckets_reclaimed``.
            mesh_gc="host-directory",
            mesh_converge_kernel=(
                "tree"
                if self.plan.replicas > 1
                and self.plan.replicas & (self.plan.replicas - 1) == 0
                else "flat"
            ),
        )
        return out
