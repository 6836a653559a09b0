"""Fault-tolerance tests: checkpoint/resume, partition-heal, and the
seeded fault-injection (faultnet) convergence suite.

The reference's fault story is by-construction (SURVEY §5): CvRDT state
tolerates loss/duplication; partitions degrade to per-side enforcement
(README.md:64-76); recovery is incast. These tests pin those properties
down explicitly — plus checkpoint/resume, which the reference lacks, and
the resilience layer's guarantees: every seeded fault schedule (drop /
dup / reorder / delay / corrupt / partition+heal) converges BIT-EXACTLY
to the no-fault fixpoint, and heal-time anti-entropy reconverges a
partitioned cluster with zero take traffic inside a bounded packet
budget. Chaos clusters run on FROZEN clocks: with now == created the
refill grant is exactly zero, so the converged lane planes are fully
deterministic and the fixpoint can be asserted bit-for-bit.
"""

import asyncio
import socket
import threading
import time

import pytest

from patrol_tpu.models.limiter import NANO, LimiterConfig
from patrol_tpu.net.faultnet import FaultNet
from patrol_tpu.ops.rate import Rate
from patrol_tpu.runtime.directory import BucketDirectory
from patrol_tpu.runtime.engine import DeviceEngine
from patrol_tpu.runtime import checkpoint as ckpt

from test_cluster import BACKEND_PARAMS, Cluster, KeepAliveClient

CFG = LimiterConfig(buckets=64, nodes=4)
RATE = Rate(freq=10, per_ns=NANO)


class TestCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        eng = DeviceEngine(CFG, node_slot=0, clock=lambda: 1000)
        try:
            eng.take("a", RATE, 3)
            eng.take("b", RATE, 7)
            ckpt.save(str(tmp_path), eng)
        finally:
            eng.stop()

        eng2 = DeviceEngine(CFG, node_slot=0, clock=lambda: 2000)
        try:
            restored = ckpt.restore(str(tmp_path), eng2)
            assert restored == 2
            # Balances and metadata survive: a has 10-3=7, b has 10-7=3.
            assert eng2.tokens("a") == 7
            assert eng2.tokens("b") == 3
            row = eng2.directory.lookup("a")
            assert eng2.directory.created_ns[row] == 1000  # original stamp
            # Resumed node keeps enforcing from where it left.
            remaining, ok, created = eng2.take("b", RATE, 3)
            assert ok and not created and remaining == 0
        finally:
            eng2.stop()

    def test_restore_is_a_join_never_a_rollback(self, tmp_path):
        """Restoring a stale checkpoint onto newer state must not roll
        anything back (elementwise max)."""
        eng = DeviceEngine(CFG, node_slot=0, clock=lambda: 0)
        try:
            eng.take("k", RATE, 2)
            ckpt.save(str(tmp_path), eng)  # stale snapshot: taken=2
            eng.take("k", RATE, 3)  # newer: taken=5
            ckpt.restore(str(tmp_path), eng)
            assert eng.tokens("k") == 5  # still 10-5, not 10-2
        finally:
            eng.stop()

    def test_shape_mismatch_rejected(self, tmp_path):
        eng = DeviceEngine(CFG, node_slot=0, clock=lambda: 0)
        try:
            ckpt.save(str(tmp_path), eng)
        finally:
            eng.stop()
        other = DeviceEngine(LimiterConfig(buckets=32, nodes=4), node_slot=0, clock=lambda: 0)
        try:
            with pytest.raises(ValueError, match="shape mismatch"):
                ckpt.restore(str(tmp_path), other)
        finally:
            other.stop()


@pytest.fixture(scope="module", params=BACKEND_PARAMS)
def cluster(request):
    """Partition/heal and loss tolerance must hold over BOTH replication
    backends: the asyncio path and the C++ recvmmsg path expose the same
    ``drop_addr`` fault-injection hook (rx-side on each node, so a
    symmetric filter partitions both directions)."""
    c = Cluster(3, udp_backend=request.param)
    yield c
    c.close()


def _set_partition(cluster, group_a, group_b):
    """Symmetric drop filter between two node-index groups."""
    node_ports = {}
    for i, cmd in enumerate(cluster.commands):
        node_ports[i] = int(cmd.node_addr.rpartition(":")[2])
    port_group = {node_ports[i]: ("a" if i in group_a else "b") for i in range(cluster.n)}

    def make_filter(my_group):
        def drop(addr):
            other = port_group.get(addr[1])
            return other is not None and other != my_group

        return drop

    for i, cmd in enumerate(cluster.commands):
        cmd.replicator.drop_addr = make_filter("a" if i in group_a else "b")


def _heal(cluster):
    for cmd in cluster.commands:
        cmd.replicator.drop_addr = None


class TestSoak:
    def test_sustained_mixed_load_leaves_invariants_clean(self):
        """Several seconds of concurrent takes (diverse keys and rates),
        bulk ingest, eviction churn, and introspection reads against one
        engine — then every bookkeeping invariant must be exactly clean:
        zero pins, empty queues, no hung tickets. This is the pin-economy
        soak: any leak on any path (deferral, eviction retry, completion
        pipeline, unknown-cap drops) shows up here."""
        import threading
        import time as _time

        import numpy as np

        from patrol_tpu.models.limiter import LimiterConfig
        from patrol_tpu.ops.rate import Rate
        from patrol_tpu.runtime.engine import DeviceEngine

        eng = DeviceEngine(
            LimiterConfig(buckets=128, nodes=8), node_slot=0
        )  # small pool ⇒ eviction churn under the keyspace below
        stop = _time.monotonic() + 4.0
        errors: list = []

        def taker(k):
            i = 0
            try:
                while _time.monotonic() < stop:
                    name = f"soak-{(i * 7 + k) % 512}"  # 4× the pool
                    rate = Rate(freq=5 + (i % 3), per_ns=NANO)
                    remaining, ok, _ = eng.take(name, rate, 1)
                    assert remaining >= 0
                    i += 1
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def ingester():
            rng = np.random.default_rng(0)
            try:
                while _time.monotonic() < stop:
                    n = 256
                    eng.ingest_deltas_batch(
                        [f"soak-{int(r)}" for r in rng.integers(0, 512, n)],
                        rng.integers(0, 8, n),
                        rng.integers(0, 3 * NANO, n),
                        rng.integers(0, NANO, n),
                        rng.integers(0, NANO, n),
                    )
                    _time.sleep(0.002)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def introspector():
            try:
                while _time.monotonic() < stop:
                    eng.snapshot("soak-1")
                    eng.tokens("soak-2")
                    _time.sleep(0.005)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = (
            [threading.Thread(target=taker, args=(k,)) for k in range(8)]
            + [threading.Thread(target=ingester), threading.Thread(target=introspector)]
        )
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive(), "soak worker hung"
        assert not errors, errors
        assert eng.flush(timeout=30), "engine never went idle"
        assert eng.directory.pins.sum() == 0, "leaked row pins"
        assert eng.backlog() == 0
        assert eng.evictions > 0, "keyspace 4x pool must have churned"
        eng.stop()


class TestPartitionHeal:
    def test_split_brain_multiplies_limit_then_heals(self, cluster):
        """Under partition each side independently enforces the limit
        (README.md:64-76: limit × partition sides); after heal the sides
        re-converge and the merged state reflects all takes."""
        clients = [KeepAliveClient(p) for p in cluster.api_ports]
        try:
            _set_partition(cluster, {0}, {1, 2})

            # Side A (node 0) admits its full burst of 6.
            a_ok = sum(
                clients[0].take("split", "6:1h")[0] == 200 for _ in range(8)
            )
            assert a_ok == 6
            # Side B (nodes 1,2) also admits its full burst — split brain.
            # Within the side, UDP propagation between nodes 1 and 2 is
            # eventually consistent, so a lagged replica can admit a bit
            # beyond capacity: ≥6 proves the partitioned side enforces
            # independently; ≤8 just bounds it by the requests sent.
            b_ok = sum(
                clients[1 + (i % 2)].take("split", "6:1h")[0] == 200
                for i in range(8)
            )
            assert 6 <= b_ok <= 8

            _heal(cluster)
            # Heal path: node 0's next take broadcast reaches side B (and
            # vice versa). Trigger one take on each side, then both sides
            # must agree the bucket is deeply overdrawn (12 taken of 6).
            deadline = time.time() + 5
            converged = False
            while time.time() < deadline and not converged:
                for cl in clients:
                    cl.take("split", "6:1h")
                views = []
                for cmd in cluster.commands:
                    cmd.engine.flush()
                    b, _ = cmd.repo.get_bucket("split")
                    views.append((b.added_nt, b.taken_nt, b.elapsed_ns))
                converged = len(set(views)) == 1 and views[0][1] >= 12 * NANO
                time.sleep(0.05)
            assert converged, f"post-heal views: {views}"
        finally:
            _heal(cluster)
            for cl in clients:
                cl.close()

    def test_packet_loss_tolerated(self, cluster):
        """50% random packet loss: convergence still happens because every
        take re-broadcasts full state (loss-tolerant by design,
        README.md:41-43)."""
        import random as _r

        rng = _r.Random(4)
        clients = [KeepAliveClient(p) for p in cluster.api_ports]
        try:
            for cmd in cluster.commands:
                cmd.replicator.drop_addr = lambda addr: rng.random() < 0.5

            for i in range(12):
                clients[i % 3].take("lossy", "5:1h")

            _heal(cluster)
            deadline = time.time() + 5
            done = False
            while time.time() < deadline and not done:
                for cl in clients:
                    cl.take("lossy", "5:1h")
                statuses = {cl.take("lossy", "5:1h")[0] for cl in clients}
                done = statuses == {429}
                time.sleep(0.05)
            assert done, "nodes did not converge to exhaustion after loss"
        finally:
            _heal(cluster)
            for cl in clients:
                cl.close()


# ---------------------------------------------------------------------------
# seeded fault-injection (faultnet) suite


def _frozen_clock_fn(i):
    # Frozen at 1s: now == created forever, so the refill grant is zero on
    # every take and the converged state is bit-deterministic.
    return lambda: NANO


def _attach_faultnets(cluster, seed, **faults):
    nets = []
    for i, cmd in enumerate(cluster.commands):
        fn = FaultNet(seed=seed + i, self_addr=cmd.node_addr)
        if faults:
            fn.link(**faults)
        cmd.replicator.faultnet = fn
        nets.append(fn)
    return nets


def _quiesce_faultnets(cluster):
    """Stop injecting faults but keep nets attached so held (delayed /
    reorder-stranded) packets still release through due()."""
    for cmd in cluster.commands:
        fn = cmd.replicator.faultnet
        if fn is not None:
            fn.heal()
            fn.link()  # default link config back to clean


def _detach_faultnets(cluster):
    for cmd in cluster.commands:
        cmd.replicator.faultnet = None


def _fast_health(cluster, probe=0.15, ttl=0.5, cap=0.4, ae_min=0.5):
    for cmd in cluster.commands:
        cmd.replicator.health.configure(
            probe_interval_s=probe, alive_ttl_s=ttl, backoff_cap_s=cap
        )
        cmd.replicator.antientropy.min_interval_s = ae_min


def _converged_views(cluster, name, deadline_s=10.0, retrigger=False):
    """Poll until every node's scalar view of ``name`` is identical;
    returns the converged (added_nt, taken_nt, elapsed_ns) tuple.
    ``retrigger``: force a fresh anti-entropy round every ~1.5s while
    waiting (an operator hammering resync), so a digest exchange that
    raced the last in-flight merges cannot leave a stable residue."""
    deadline = time.time() + deadline_s
    next_trigger = 0.0
    views = []
    while time.time() < deadline:
        if retrigger and time.time() >= next_trigger:
            next_trigger = time.time() + 1.5
            for cmd in cluster.commands:
                for peer in cmd.replicator.peers:
                    cmd.replicator.antientropy.trigger(peer, force=True)
        views = []
        for cmd in cluster.commands:
            cmd.engine.flush()
            row = cmd.engine.directory.lookup(name)
            if row is None:
                views.append(None)
                continue
            pn, elapsed = cmd.engine.row_view(row)
            base = int(cmd.engine.directory.cap_base_nt[row])
            views.append(
                (base + int(pn[:, 0].sum()), int(pn[:, 1].sum()), int(elapsed))
            )
        if None not in views and len(set(views)) == 1:
            # Quiescence, not just agreement: on the delta plane an
            # unacked interval is retransmittable state still in flight —
            # two nodes can transiently AGREE one delta short of the
            # fixpoint while the retransmit waits out its tick budget.
            # (Seen as a rare 15/16-takes false convergence.)
            pending = sum(
                cmd.replicator.delta.stats().get("wire_intervals_unacked", 0)
                for cmd in cluster.commands
                if getattr(cmd.replicator, "delta", None) is not None
            )
            if pending == 0:
                return views[0]
        time.sleep(0.05)
    raise AssertionError(f"views did not converge: {views}")


def _lane_planes(cluster, name):
    out = []
    for cmd in cluster.commands:
        row = cmd.engine.directory.lookup(name)
        pn, elapsed = cmd.engine.row_view(row)
        out.append((pn.copy(), int(elapsed)))
    return out


SCHEDULES = {
    "drop": dict(drop=0.4),
    "dup": dict(dup=0.5),
    "reorder": dict(reorder=0.5),
    "delay": dict(delay_s=0.05),
    "corrupt": dict(corrupt=0.4),
}


@pytest.fixture(scope="module", params=BACKEND_PARAMS)
def chaos_cluster(request):
    # python HTTP front: the native front's epoll thread takes time from
    # CLOCK_REALTIME, which would re-introduce wall-clock refill grants
    # and break the bit-exact fixpoint assertions.
    c = Cluster(
        3,
        udp_backend=request.param,
        clock_fn=_frozen_clock_fn,
        http_front="python",
    )
    _fast_health(c)
    yield c
    c.close()


@pytest.mark.chaos
class TestSeededFaultSchedules:
    """Acceptance: every seeded fault schedule converges bit-exactly to
    the no-fault fixpoint after heal. The workload is one fault-free
    priming take per node followed by 12 chaos-phase takes round-robin
    against a 100-token bucket — every take is admitted regardless of
    fault interleaving, and with frozen clocks the no-fault fixpoint is
    exactly: added lanes all zero, taken lane of node i = 5·NANO,
    elapsed 0, aggregate (100·NANO, 15·NANO, 0)."""

    @pytest.mark.parametrize("kind", sorted(SCHEDULES))
    def test_schedule_converges_to_no_fault_fixpoint(self, chaos_cluster, kind):
        cluster = chaos_cluster
        bucket = f"chaos-{kind}"
        clients = [KeepAliveClient(p) for p in cluster.api_ports]
        # Prime: one fault-free take per node, converged, BEFORE injecting
        # faults. Bucket creation has a documented sub-µs residency race
        # (engine._host_serve_ticket: an rx echo concurrent with the very
        # first take can strand one delta in the device plane) that is
        # accepted by design and orthogonal to what this suite pins down —
        # the chaos phase must run against established buckets.
        for cl in clients:
            status, _ = cl.take(bucket, "100:1h")
            assert status == 200
        assert _converged_views(cluster, bucket) == (100 * NANO, 3 * NANO, 0)
        nets = _attach_faultnets(cluster, seed=42, **SCHEDULES[kind])
        try:
            for i in range(12):
                status, _ = clients[i % 3].take(bucket, "100:1h")
                assert status == 200  # 100 ≫ 15: always admitted
                time.sleep(0.005)
            _quiesce_faultnets(cluster)
            time.sleep(0.2)  # let queued (undropped) merges settle
            # Heal-time reconciliation, explicitly force-triggered while
            # polling (the drop/dup class keeps peers alive throughout, so
            # there is no dead→alive edge to auto-trigger on — that path
            # is covered by TestPartitionHealAntiEntropy).
            view = _converged_views(cluster, bucket, retrigger=True)
            assert view == (100 * NANO, 15 * NANO, 0)
            # Bit-exact lane planes on every node: the no-fault fixpoint
            # (1 prime take + 4 chaos takes per node, no grants, elapsed 0).
            slots = [cmd.replicator.slots.self_slot for cmd in cluster.commands]
            for pn, elapsed in _lane_planes(cluster, bucket):
                assert elapsed == 0
                assert int(pn[:, 0].sum()) == 0  # frozen clock: no grants
                for node_i, slot in enumerate(slots):
                    assert pn[slot, 1] == 5 * NANO, (
                        f"{kind}: node {node_i} lane lost takes"
                    )
            # The schedule actually injected its fault class.
            total = {k: sum(fn.stats()[f"faultnet_{k}"] for fn in nets)
                     for k in ("dropped", "duplicated", "reordered", "delayed",
                               "corrupted")}
            key = {"drop": "dropped", "dup": "duplicated",
                   "reorder": "reordered", "delay": "delayed",
                   "corrupt": "corrupted"}[kind]
            assert total[key] > 0, f"schedule {kind} injected nothing"
            if kind == "corrupt":
                # Corrupt packets must be REJECTED at decode, not merged.
                assert sum(
                    cmd.replicator.rx_errors for cmd in cluster.commands
                ) > 0
        finally:
            _detach_faultnets(cluster)
            for cl in clients:
                cl.close()


@pytest.mark.chaos
class TestPartitionHealAntiEntropy:
    """Acceptance: heal-time anti-entropy reconverges a 3-node cluster
    after a timed partition WITHOUT take traffic — digests + targeted
    incast only, inside an asserted packet budget."""

    def test_heal_reconverges_without_takes_within_packet_budget(
        self, chaos_cluster
    ):
        cluster = chaos_cluster
        nets = _attach_faultnets(cluster, seed=7)
        clients = [KeepAliveClient(p) for p in cluster.api_ports]
        try:
            # A pre-synced control bucket: converged BEFORE the partition,
            # so the heal exchange must not re-ship it (targeting proof).
            for _ in range(2):
                clients[0].take("ae-stable", "50:1h")
            _converged_views(cluster, "ae-stable")
            # Prime the divergence bucket fault-free too (the engine's
            # documented bucket-creation residency race is out of scope).
            for cl in clients:
                cl.take("ae-heal", "100:1h")
            _converged_views(cluster, "ae-heal")

            addrs = [cmd.node_addr for cmd in cluster.commands]
            for fn in nets:
                fn.partition([addrs[0]], [addrs[1], addrs[2]])
            time.sleep(0.8)  # > alive_ttl: cross-side peers go dead
            # Divergent spend on both sides, then total silence.
            for _ in range(3):
                clients[0].take("ae-heal", "100:1h")
            for i in range(4):
                clients[1 + i % 2].take("ae-heal", "100:1h")
            time.sleep(0.3)  # let intra-side replication settle
            # Counters are cumulative over the module-scoped cluster:
            # assert DELTAS across the heal window.
            # patrol-fleet metrics gossip is constant-rate background
            # traffic (paced, bounded) — the budget below asserts the
            # HEAL exchange's cost, so gossip datagrams are netted out.
            before = [cmd.replicator.stats() for cmd in cluster.commands]
            tx_before = sum(
                s["replication_tx_packets"] - s.get("fleet_packets_tx", 0)
                for s in before
            )
            for fn in nets:
                fn.heal()
            # NO take traffic from here: probes revive the dead links,
            # the dead→alive edge auto-triggers the digest exchange, and
            # only the divergent bucket is fetched/pushed.
            view = _converged_views(cluster, "ae-heal")
            assert view == (100 * NANO, 10 * NANO, 0)
            tx_spent = sum(
                cmd.replicator.stats()["replication_tx_packets"]
                - cmd.replicator.stats().get("fleet_packets_tx", 0)
                for cmd in cluster.commands
            ) - tx_before
            # Budget: probes + acks + digests + fetches + pushes for ONE
            # divergent bucket across 4 healed directed pairs. An
            # untargeted resync (or a storm) blows well past this.
            assert tx_spent <= 250, f"heal cost {tx_spent} packets"
            after = [cmd.replicator.stats() for cmd in cluster.commands]

            def delta(key):
                return sum(a[key] - b[key] for a, b in zip(after, before))

            assert delta("ae_triggers") >= 1
            assert delta("resync_buckets") >= 1
            # Targeting: only the divergent bucket is fetched — never the
            # pre-synced one. Each healed directed pair fetches ≤ 1 bucket
            # per digest round; damping bounds rounds inside the window.
            assert 1 <= delta("ae_fetches_tx") <= 16
            assert delta("peer_heals") >= 2
        finally:
            _detach_faultnets(cluster)
            for cl in clients:
                cl.close()


@pytest.mark.chaos
class TestIngestIdempotence:
    """Satellite: reordered/duplicated wire packets are idempotent at
    ingest — the same packet set lands on the same bit-exact planes in any
    order, any multiplicity, through the real codec."""

    def test_reordered_duplicated_wire_packets_land_identically(self):
        from patrol_tpu.ops import wire

        cfg = LimiterConfig(buckets=16, nodes=4)
        # A realistic broadcast history: three nodes' successive
        # full-state packets for one bucket, each later packet subsuming
        # the earlier (monotone lanes), interleaved across senders.
        packets = []
        for step in range(1, 5):
            for slot in range(3):
                packets.append(
                    wire.encode(
                        wire.from_nanotokens(
                            "idem", (10 + step) * NANO, step * NANO,
                            step * 10, origin_slot=slot, cap_nt=10 * NANO,
                            lane_added_nt=step * NANO // 2,
                            lane_taken_nt=step * NANO,
                        )
                    )
                )

        def apply(sequence):
            eng = DeviceEngine(cfg, node_slot=3, clock=lambda: NANO)
            try:
                for data in sequence:
                    st = wire.decode(data)
                    eng.ingest_delta(st, st.origin_slot)
                assert eng.flush(timeout=30)
                row = eng.directory.lookup("idem")
                pn, elapsed = eng.read_rows([row])
                return pn[0].copy(), int(elapsed[0])
            finally:
                eng.stop()

        import random as _r

        shuffled = list(packets)
        _r.Random(13).shuffle(shuffled)
        baseline = apply(packets)
        reordered = apply(list(reversed(packets)))
        duplicated = apply([p for p in packets for _ in range(2)])
        shuffled_dup = apply(shuffled + shuffled)
        for other in (reordered, duplicated, shuffled_dup):
            assert (baseline[0] == other[0]).all()
            assert baseline[1] == other[1]


# ---------------------------------------------------------------------------
# wire v2: delta-interval plane under chaos


@pytest.mark.chaos
class TestDeltaWireChaos:
    """Satellite (wire v2): drop/dup/reorder schedules over DELTA-MODE
    links converge bit-exactly to the no-fault fixpoint on frozen clocks —
    the interval retransmit machinery is the repair path — and an
    interval-loss schedule that overflows the ack window falls back to
    full-state repair (anti-entropy handoff) and heals within a bounded
    packet budget."""

    RATE100 = Rate(freq=100, per_ns=3600 * NANO)

    def _delta_cluster(self):
        c = Cluster(
            2,
            udp_backend="asyncio",
            wire_mode="delta",
            clock_fn=_frozen_clock_fn,
            http_front="python",
        )
        _fast_health(c)
        return c

    def _wait_capable(self, c, deadline_s=10.0):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if all(
                len(cmd.replicator.delta.capable_peers()) == c.n - 1
                for cmd in c.commands
            ):
                return
            time.sleep(0.05)
        raise AssertionError("v2 capability handshake did not complete")

    def test_drop_dup_reorder_converges_to_no_fault_fixpoint(self):
        c = self._delta_cluster()
        try:
            self._wait_capable(c)
            _attach_faultnets(c, seed=77, drop=0.3, dup=0.3, reorder=0.3)
            for t in range(16):
                _, ok = c.commands[t % 2].repo.take("delta-chaos", self.RATE100, 1)
                assert ok, "admission under chaos must not fail at 100 >> 16"
                time.sleep(0.01)
            _quiesce_faultnets(c)
            view = _converged_views(c, "delta-chaos", deadline_s=15, retrigger=True)
            # No-fault fixpoint, bit-exact: zero grants on frozen clocks,
            # 16 takes of 1 token.
            assert view == (100 * NANO, 16 * NANO, 0)
            # The delta plane actually carried the data (not a silent
            # classic fallback), and faults actually fired.
            stats = [cmd.replicator.stats() for cmd in c.commands]
            assert all(s["wire_delta_packets_tx"] > 0 for s in stats)
            assert all(s["wire_deltas_batched"] > 0 for s in stats)
            assert (
                sum(
                    cmd.replicator.faultnet.dropped
                    + cmd.replicator.faultnet.duplicated
                    for cmd in c.commands
                )
                > 0
            )
        finally:
            c.close()

    def test_interval_loss_falls_back_to_fullstate_and_heals_bounded(self):
        c = self._delta_cluster()
        try:
            self._wait_capable(c)
            r0 = c.commands[0].replicator
            r1 = c.commands[1].replicator
            # Force the GC-overflow path: never retransmit, tiny window.
            r0.delta.retransmit_ticks = 10**9
            r0.delta.max_unacked_intervals = 2
            fn = FaultNet(seed=3, self_addr=c.commands[0].node_addr)
            fn.link(drop=1.0)  # node0 hears nothing: every ack is lost
            r0.faultnet = fn
            takes = 0
            deadline = time.time() + 15
            while (
                time.time() < deadline
                and r0.delta.stats()["wire_fullstate_fallbacks"] == 0
            ):
                _, ok = c.commands[0].repo.take("fallback", self.RATE100, 1)
                assert ok
                takes += 1
                time.sleep(0.05)
            st = r0.delta.stats()
            assert st["wire_fullstate_fallbacks"] >= 1
            # The fallback renegotiates capability and hands repair to AE.
            # Heal the link and require reconvergence to the exact
            # fixpoint within a bounded packet budget.
            tx_before = r0.tx_packets + r1.tx_packets
            r0.faultnet = None
            view = _converged_views(c, "fallback", deadline_s=15, retrigger=True)
            assert view == (100 * NANO, takes * NANO, 0)
            heal_packets = (r0.tx_packets + r1.tx_packets) - tx_before
            assert heal_packets <= 250, f"heal used {heal_packets} packets"
        finally:
            c.close()


@pytest.mark.chaos
class TestGcChaos:
    """Bucket lifecycle under faults (ROADMAP item 4): idle-bucket GC
    firing on ONE side of a partition collects only the full bucket that
    holds no peer lane (a peer lane is an acknowledged remote write, kept)
    and must still reconverge bit-exactly to the no-fault fixpoint via AE
    after heal — the collected bucket reads as zero-state (its own-lane
    residue tombstoned and re-seeded), never as unknown. Clocks are injected with ONE
    deterministic jump (t0 -> t1): grants are zero at t0 and exactly
    computable at t1, so the converged lane planes are bit-deterministic
    like the rest of the chaos suite."""

    def _two_nodes(self, seed=2027):
        import asyncio

        from patrol_tpu.net.replication import Replicator, SlotTable
        from patrol_tpu.runtime.repo import TPURepo

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
            s.close()
            return port

        loop = asyncio.new_event_loop()
        thread = threading.Thread(
            target=lambda: (asyncio.set_event_loop(loop), loop.run_forever()),
            daemon=True,
        )
        thread.start()

        def on_loop(coro):
            return asyncio.run_coroutine_threadsafe(coro, loop).result(15)

        addrs = [f"127.0.0.1:{free_port()}" for _ in range(2)]
        clocks = [{"now": NANO}, {"now": NANO}]
        nodes = []
        for i in range(2):
            slots = SlotTable(addrs[i], addrs, max_slots=4)
            rep = on_loop(Replicator.create(addrs[i], addrs, slots))
            rep.health.configure(
                probe_interval_s=0.15, alive_ttl_s=0.5, backoff_cap_s=0.4
            )
            rep.antientropy.min_interval_s = 0.2
            fn = FaultNet(seed=seed + i, self_addr=addrs[i])
            fn.link(drop=0.2, dup=0.2, reorder=0.2)
            rep.faultnet = fn
            eng = DeviceEngine(
                CFG, node_slot=slots.self_slot,
                clock=(lambda c=clocks[i]: c["now"]),
            )
            eng.configure_lifecycle(window_ms=0)  # manual, deterministic
            repo = TPURepo(eng, send_incast=rep.send_incast_request)
            rep.repo = repo
            eng.on_broadcast = rep.broadcast_states
            nodes.append((rep, eng, repo, fn))
        return loop, thread, on_loop, addrs, clocks, nodes

    def _converge(self, nodes, names, deadline_s=15):
        deadline = time.time() + deadline_s
        next_trigger = 0.0
        while time.time() < deadline:
            if time.time() >= next_trigger:
                next_trigger = time.time() + 0.5
                for rep, _, _, _ in nodes:
                    for peer in rep.peers:
                        rep.antientropy.trigger(peer, force=True)
            views = []
            for _, eng, _, _ in nodes:
                eng.flush()
                per = []
                for name in names:
                    row = eng.directory.lookup(name)
                    if row is None:
                        per.append(None)
                        continue
                    pn, el = eng.row_view(row)
                    per.append((pn.tolist(), int(el)))
                views.append(tuple(map(tuple, [(n,) for n in names])) and per)
            if all(v is not None for view in views for v in view) and all(
                view == views[0] for view in views
            ):
                return views[0]
            time.sleep(0.05)
        raise AssertionError(f"no convergence: {views}")

    def _run_scenario(self, gc: bool, seed=2027):
        rate_fast = Rate(freq=10, per_ns=NANO)  # refills 10/s: collectable
        rate_slow = Rate(freq=10, per_ns=3600 * NANO)  # ~no refill at t1
        loop, thread, on_loop, addrs, clocks, nodes = self._two_nodes(seed)
        outcomes = []
        try:
            # Phase 1 (t0): spend on both nodes with a convergence
            # barrier between them — each node takes against the
            # CONVERGED fixpoint, so per-take outcomes are deterministic
            # even though the links drop/dup/reorder (AE repairs).
            names = ["gc0", "gc1", "gc2", "slow", "own0"]
            for i, (rep, eng, repo, fn) in enumerate(nodes):
                for k in range(3):
                    outcomes.append(repo.take(f"gc{k}", rate_fast, 1 + i))
                    assert outcomes[-1][1]
                outcomes.append(repo.take("slow", rate_slow, 2))
                assert outcomes[-1][1]
                if i == 0:  # spent on node 0 alone: no peer lane there
                    outcomes.append(repo.take("own0", rate_fast, 3))
                    assert outcomes[-1][1]
                self._converge(nodes, names)

            # Phase 2: partition, jump both clocks to t1 (+5s: the fast-
            # rate buckets fully refill; the slow one cannot).
            for rep, _, _, fn in nodes:
                fn.partition([addrs[0]], [addrs[1]])
            for c in clocks:
                c["now"] = NANO + 5 * NANO
            reclaimed = 0
            if gc:
                reclaimed = nodes[0][1].gc_sweep(force=True)
                # Only own0 collects: the refilled gc* buckets hold node
                # 1's lanes, and the slow one is not full.
                assert reclaimed == 1, f"reclaimed {reclaimed}"
                assert nodes[0][1].directory.lookup("own0") is None
                assert nodes[0][1].directory.lookup("gc0") is not None
                assert nodes[0][1].directory.lookup("slow") is not None
            # Both nodes keep spending mid-partition; AE re-creates own0
            # on node 0 after heal — the tombstone re-seed path under
            # faults.
            outcomes.append(nodes[1][2].take("gc0", rate_fast, 4))
            assert outcomes[-1][1]
            outcomes.append(nodes[0][2].take("gc0", rate_fast, 2))
            assert outcomes[-1][1]

            # Phase 3: heal; AE must reconverge every bucket bit-exactly.
            for rep, _, _, fn in nodes:
                fn.heal()
                fn.link()
            view = self._converge(nodes, names)
            # Canonicalize lane order by NODE (slot numbers depend on
            # the run's random ports): [node0's lane, node1's lane, rest].
            slots = [eng.node_slot for _, eng, _, _ in nodes]
            rest = [s for s in range(4) if s not in slots]
            order = slots + rest
            view = [
                ([pn[s] for s in order], el) for pn, el in view
            ]
            return view, reclaimed, outcomes
        finally:
            for rep, eng, _, _ in nodes:
                loop.call_soon_threadsafe(rep.close)
                eng.stop()
            time.sleep(0.2)
            loop.call_soon_threadsafe(loop.stop)
            thread.join(timeout=5)

    def test_gc_mid_partition_reconverges_to_no_gc_fixpoint(self):
        from patrol_tpu.ops.lifecycle import host_reconstructed_nt

        view_gc, reclaimed, out_gc = self._run_scenario(gc=True)
        view_ref, _, out_ref = self._run_scenario(gc=False)
        assert reclaimed == 1
        # Take outcomes are IDENTICAL with and without GC — no admission
        # decision ever changed (the soak gate's law, under faults).
        assert out_gc == out_ref
        for (pn_gc, el_gc), (pn_ref, el_ref) in zip(view_gc, view_ref):
            # Conservation, bit-exact: the TAKEN lanes (admitted spend,
            # incl. forfeits) and the refill clock converge identically —
            # node0's post-reclaim spend resumed ON TOP of its tombstone,
            # so node1's stale echo absorbed nothing.
            assert [lane[1] for lane in pn_gc] == [lane[1] for lane in pn_ref]
            assert el_gc == el_ref
            # Refill grants committed mid-partition may be SMALLER on the
            # GC side (it granted against a view without the dropped
            # peer-lane cache — information the partition withheld):
            # strictly conservative, never an extra token.
            assert all(
                g[0] <= r[0] for g, r in zip(pn_gc, pn_ref)
            ), (pn_gc, pn_ref)
        # And the transient grant gap is exactly refill accounting: at
        # the refill fixpoint (t2 >> t1) every bucket reconstructs to
        # the same balance in both runs, bit for bit.
        t2 = 100 * NANO
        for (pn_gc, el_gc), (pn_ref, el_ref), per in zip(
            view_gc, view_ref, [NANO, NANO, NANO, 3600 * NANO, NANO]
        ):
            rec_gc = int(host_reconstructed_nt(
                sum(l[0] for l in pn_gc), sum(l[1] for l in pn_gc),
                el_gc, 10 * NANO, NANO, t2, per,
            ))
            rec_ref = int(host_reconstructed_nt(
                sum(l[0] for l in pn_ref), sum(l[1] for l in pn_ref),
                el_ref, 10 * NANO, NANO, t2, per,
            ))
            assert rec_gc == rec_ref
