"""Process supervisor (reference: ``Command``, command.go:17-83).

Wires storage (device engine) + replication (UDP) + API (HTTP) into one
process and supervises them — the reference's ``oklog/run`` actor group
becomes an asyncio task group with signal handling and a graceful-shutdown
timeout. Used by both the CLI (cmd/patrol/main.go) and the in-process
multi-node cluster tests (≙ command_test.go:13-77).
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import logging
import signal
import time
from typing import List, Optional

from patrol_tpu.models.limiter import LimiterConfig, SMALL
from patrol_tpu.net.api import API, serve
from patrol_tpu.net.replication import Replicator, SlotTable
from patrol_tpu.runtime.bucket import ClockFn, system_clock
from patrol_tpu.runtime.engine import DeviceEngine
from patrol_tpu.runtime.repo import TPURepo


@dataclasses.dataclass
class Command:
    """All runtime config funnels into this struct (≙ command.go:18-25),
    which doubles as the test-harness entry point."""

    api_addr: str = "127.0.0.1:8080"
    node_addr: str = "127.0.0.1:16000"
    # Human-meaningful node identity for fleet views (patrol-fleet lane
    # attribution: /debug/vars histogram summaries, /cluster/* labels).
    # Defaults to node_addr.
    node_name: str = ""
    peer_addrs: List[str] = dataclasses.field(default_factory=list)
    clock: ClockFn = system_clock  # the injected-clock seam (command.go:23)
    shutdown_timeout_s: float = 30.0
    config: LimiterConfig = SMALL
    log: Optional[logging.Logger] = None
    handle_signals: bool = True
    # "native" = C++ recvmmsg/sendmmsg path, "asyncio" = pure python,
    # "auto" = native when the toolchain built it, else asyncio.
    udp_backend: str = "auto"
    # Outgoing wire form: "delta" (the DEFAULT since the wire-v2 bake:
    # batched delta-interval datagrams to capability-advertising peers,
    # aggregate full-state to the rest — the handshake keeps mixed
    # v1/v2 clusters safe), "full"/"aggregate" (the per-take full-state
    # opt-out; dual-payload headers, flag-day upgrade from
    # pre-lane-trailer patrol_tpu builds), or "compat" (raw own-lane
    # headers for rolling upgrades). See ops/wire.py and net/delta.py.
    wire_mode: str = "delta"
    # HTTP front: "native" = C++ epoll front (net/native_http.py) — the
    # /take decision runs entirely in-process for host-resident buckets
    # (the reference's performance class, api.go:51-86) and h2c clients
    # splice to a loopback python h2 server, so protocol parity holds;
    # "python" = asyncio server, the protocol-reference implementation;
    # "auto" (default) = native when the toolchain built it, else python.
    # r4 kept python as default for h2c; the r5 in-front take path plus
    # the h2c splice makes native strictly better when available.
    http_front: str = "auto"
    # Checkpoint/resume (the reference has none, SURVEY §5): restore at
    # boot when the directory holds a snapshot; save every interval (0 ⇒
    # only at shutdown) and at graceful shutdown.
    checkpoint_dir: Optional[str] = None
    checkpoint_interval_s: float = 0.0
    # Pre-compile all kernel batch variants at boot (kills JIT p99 spikes;
    # adds seconds to startup — off for tests, on for production/bench).
    warmup: bool = False
    # Multi-device: >0 runs the MeshEngine over all local devices with this
    # many full replicas (the rest of the devices become bucket shards);
    # 0 = single-device engine.
    mesh_replicas: int = 0

    # Populated by run() for tests/introspection.
    engine: Optional[DeviceEngine] = None
    repo: Optional[TPURepo] = None
    replicator: Optional[Replicator] = None
    # Set by run() once every socket is bound and the API is accepting —
    # the deterministic "serving" signal for supervisors and tests
    # (awaitable immediately after construction; cleared when run() begins).
    started: asyncio.Event = dataclasses.field(default_factory=asyncio.Event)

    async def run(self, stop: Optional[asyncio.Event] = None) -> None:
        """Run until ``stop`` is set or SIGINT/SIGTERM arrives; then shut
        down gracefully (drain HTTP, stop engine) within the timeout
        (command.go:46-82)."""
        if self.shutdown_timeout_s <= 0:
            raise ValueError("shutdown_timeout_s must be set")
        log = self.log or logging.getLogger("patrol")
        stop = stop or asyncio.Event()
        self.started.clear()

        from patrol_tpu.runtime import checkpoint as ckpt

        # Rejoin pinning (patrol-membership): a restarting node must come
        # back on its ORIGINAL lane — its checkpointed PN spend lives
        # there — even when its peer list changed (rolling restart under a
        # new address). The checkpoint's membership meta carries the lane;
        # without it, rank-order assignment could hand self a different
        # lane and strand the restored spend where stale echoes absorb it.
        self_slot = None
        mem = None
        if self.checkpoint_dir and ckpt.exists(self.checkpoint_dir):
            mem = ckpt.load_membership(self.checkpoint_dir)
            if mem is not None and isinstance(mem.get("self_slot"), int):
                self_slot = mem["self_slot"]
        slots = SlotTable(
            self.node_addr,
            self.peer_addrs,
            max_slots=self.config.nodes,
            self_slot=self_slot,
        )
        if mem is not None:
            # The epoch counter survives restarts (monotone; a reborn
            # admin must never re-issue historical epochs).
            slots.restore_epoch(mem.get("epoch"))
        from patrol_tpu.utils import histogram as hist_mod

        # Node identity rides every histogram summary and gossip packet,
        # so merged fleet views attribute lanes without guessing.
        node_name = self.node_name or self.node_addr
        hist_mod.set_node_identity(slots.self_slot, node_name)
        http_front = self.http_front
        if http_front == "auto":
            from patrol_tpu.net import native_http as _nh

            http_front = "native" if _nh.available() else "python"
        if self.mesh_replicas > 0:
            from patrol_tpu.runtime.mesh_engine import MeshEngine

            engine = MeshEngine(
                self.config,
                replicas=self.mesh_replicas,
                node_slot=slots.self_slot,
                clock=self.clock,
            )
        else:
            engine = DeviceEngine(
                self.config,
                node_slot=slots.self_slot,
                clock=self.clock,
                # Native front ⇒ host-resident lanes live in the C++ store
                # and /take is served on the epoll thread (api.go:51-86's
                # in-process shape); python front keeps the pure-Python
                # host tier.
                native_host=(http_front == "native"),
            )

        from patrol_tpu.net import native_replication

        use_native = self.udp_backend == "native" or (
            self.udp_backend == "auto" and native_replication.available()
        )
        if use_native:
            replicator = native_replication.NativeReplicator(
                self.node_addr, self.peer_addrs, slots, log_=log,
                wire_mode=self.wire_mode,
            )
        else:
            replicator = await Replicator.create(
                self.node_addr, self.peer_addrs, slots, log=log,
                wire_mode=self.wire_mode,
            )
        repo = TPURepo(engine, send_incast=replicator.send_incast_request)
        replicator.repo = repo
        engine.on_broadcast = replicator.broadcast_states
        if getattr(replicator, "fleet", None) is not None:
            replicator.fleet.set_identity(node_name)

        if self.checkpoint_dir and ckpt.exists(self.checkpoint_dir):
            n = ckpt.restore(self.checkpoint_dir, engine)
            log.info("checkpoint restored", extra={"buckets": n, "dir": self.checkpoint_dir})

        warmup_s = None
        if self.warmup:
            t0 = asyncio.get_running_loop().time()
            await asyncio.get_running_loop().run_in_executor(None, engine.warmup)
            warmup_s = asyncio.get_running_loop().time() - t0
            log.info("kernels warmed", extra={"seconds": round(warmup_s, 2)})
        log.debug(
            "peers",
            extra={
                "self": self.node_addr,
                "slot": slots.self_slot,
                "others": [f"{h}:{p}" for h, p in replicator.peers],
            },
        )

        def stats() -> dict:
            from patrol_tpu.utils import histogram as hist_mod
            from patrol_tpu.utils import profiling

            threads = profiling.thread_cpu_ns()
            return {
                "engine_ticks": engine.ticks,
                "engine_evictions": engine.evictions,
                "engine_scalar_dropped": engine.scalar_dropped,
                "engine_pending_completions": engine.pending_completions,
                # Work not yet applied: queued rows, and a drained tick
                # still compiling or dispatching.
                "engine_backlog": engine.backlog(),
                "engine_tick_busy": engine.tick_busy,
                "engine_hosted_buckets": engine.hosted_buckets,
                "engine_host_takes": engine.host_takes,
                "engine_promotions": engine.promotions,
                "engine_demotions": engine.demotions,
                "buckets": len(engine.directory),
                "node_slot": slots.self_slot,
                # CLOCK_MONOTONIC now: the clock of the flight recorder's
                # ring, the C++ front's parse stamps and a capture's
                # patrol.clock anchor; its difference over a window is
                # the window's length on the node.
                "mono_ns": time.monotonic_ns(),
                # CPU ns of every live Python thread by name, and their
                # sum: a difference over a window, against mono_ns's, is
                # a thread's share of a core.
                "thread_cpu_ns": threads,
                "py_threads_cpu_ns": sum(threads.values()),
                # Bucket lifecycle (idle-bucket GC + memory budget):
                # live gauges — reclaim/shed/compaction counts, bytes in
                # use vs budget, tombstones, pressure level.
                **engine.lifecycle_stats(),
                # Mesh serving (MeshEngine only): replica/shard geometry,
                # fused-dispatch accounting, and the machine-readable
                # `mesh_demotion: unsupported` residency constraint.
                **(engine.stats() if hasattr(engine, "stats") else {}),
                # Device-commit pipeline counters (staging reuse, commit
                # coalescing, dispatch-ahead depth, rx staging).
                **profiling.COUNTERS.snapshot(),
                **replicator.stats(),
                # patrol-scope latency histograms (count/p50/p99/max per
                # stage) — the /debug/vars view; /metrics exposes the
                # full cumulative-bucket form of the same histograms.
                "histograms": hist_mod.HISTOGRAMS.snapshot(),
                # The backend, the selected kernels, device memory and
                # the boot's warm-up time (None = warm-up off).
                "device": {**engine.device_vars(), "warmup_s": warmup_s},
            }

        api = API(repo, log=log, stats=stats)
        # /cluster/* (patrol-fleet): served from the replicator's gossip
        # store — any node answers for the fleet.
        api.fleet = getattr(replicator, "fleet", None)
        # /debug/audit (patrol-audit): the consistency plane's gauges.
        api.audit = getattr(replicator, "audit", None)
        # /admin/peers (patrol-membership): runtime join/leave/rejoin.
        api.membership = getattr(replicator, "membership", None)
        host, _, port = self.api_addr.rpartition(":")
        native_front = None
        server = None
        if http_front == "native":
            from patrol_tpu.net import native_http

            native_front = native_http.NativeHTTPFront(
                api, host or "127.0.0.1", int(port)
            )
            # h2c parity (command.go:41-44): a loopback python h2 server
            # receives preface-bearing connections spliced through the
            # C++ front, so `--http-front native` speaks BOTH protocols
            # (h1 on the fast path, h2 at the python front's throughput).
            server = await serve(api, "127.0.0.1", 0)
            h2_port = server.sockets[0].getsockname()[1]
            native_front.set_h2_backend(h2_port)
            base_stats = stats

            def stats_with_http() -> dict:  # /debug/vars includes the front
                return {
                    **base_stats(),
                    **native_front.stats(),
                    "h2_backend_port": h2_port,
                }

            api.stats = stats_with_http
        else:
            server = await serve(api, host or "127.0.0.1", int(port))

        self.engine, self.repo, self.replicator = engine, repo, replicator

        if self.handle_signals:
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                with contextlib.suppress(NotImplementedError, RuntimeError):
                    loop.add_signal_handler(sig, stop.set)

        log.info("API serving", extra={"addr": self.api_addr})
        self.started.set()

        # Membership meta rides every checkpoint so a restart (possibly
        # under a new address) can pin itself back onto its original lane.
        def _membership_meta():
            mem = getattr(replicator, "membership", None)
            return mem.view() if mem is not None else None

        ckpt_task = None
        if self.checkpoint_dir and self.checkpoint_interval_s > 0:
            loop = asyncio.get_running_loop()

            async def _periodic_checkpoint():
                while True:
                    await asyncio.sleep(self.checkpoint_interval_s)
                    try:
                        await loop.run_in_executor(
                            None,
                            ckpt.save,
                            self.checkpoint_dir,
                            engine,
                            _membership_meta(),
                        )
                    except Exception:  # pragma: no cover
                        log.exception("periodic checkpoint failed")

            ckpt_task = asyncio.ensure_future(_periodic_checkpoint())

        try:
            await stop.wait()
        finally:
            if ckpt_task is not None:
                ckpt_task.cancel()
            if self.checkpoint_dir:
                try:
                    ckpt.save(self.checkpoint_dir, engine, _membership_meta())
                    log.info("checkpoint saved", extra={"dir": self.checkpoint_dir})
                except Exception:  # pragma: no cover
                    log.exception("final checkpoint failed")
            log.info("shutting down")
            # Graceful-shutdown flush: re-broadcast the final state of
            # recently-active buckets (bounded, paced) BEFORE the transport
            # closes, so a clean restart doesn't silently shed recent takes
            # whose last organic broadcast was lost. Best-effort: any
            # failure degrades to the old behavior (peers re-learn the
            # state via incast on next contact).
            try:
                states = (
                    engine.drain_dirty_states(limit=1024)
                    if replicator.peers
                    else []
                )
                for lo in range(0, len(states), 64):
                    replicator.broadcast_states(states[lo : lo + 64])
                    await asyncio.sleep(0.002)  # pace; lets the loop send
                if states:
                    from patrol_tpu.utils import profiling

                    profiling.COUNTERS.inc("shutdown_flush_states", len(states))
                    log.info(
                        "shutdown flush", extra={"states": len(states)}
                    )
            except Exception:  # pragma: no cover
                log.exception("shutdown flush failed")
            if server is not None:
                server.close()
                with contextlib.suppress(asyncio.TimeoutError):
                    await asyncio.wait_for(
                        server.wait_closed(), timeout=self.shutdown_timeout_s
                    )
            if native_front is not None:
                await asyncio.get_running_loop().run_in_executor(
                    None, native_front.close
                )
            replicator.close()
            engine.stop()
            for handler in (self.log.handlers if self.log else []):
                with contextlib.suppress(Exception):
                    handler.flush()  # ≙ Log.Sync() (command.go:38)
            self.started.clear()  # no stale "serving" signal after shutdown
