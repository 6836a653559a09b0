"""Bucket lifecycle tests: idle-bucket GC (the IsZero reclaim rule),
tombstone re-seeding, memory-budget enforcement with load shedding, and
the conservation law the design exists for — a peer's stale echo of a
reclaimed bucket's old lanes must never erase post-reclaim spend.

All clocks are injected and advanced explicitly; GC is driven via
``gc_sweep()`` / ``configure_lifecycle()`` (the feeder cadence is pinned
off under test — see tests/conftest.py).
"""

import numpy as np
import pytest

from patrol_tpu.models.limiter import NANO, LimiterConfig
from patrol_tpu.ops import wire
from patrol_tpu.ops.rate import Rate
from patrol_tpu.runtime.directory import OverloadedError
from patrol_tpu.runtime.engine import DeviceEngine
from patrol_tpu.utils import profiling, slo

CFG = LimiterConfig(buckets=64, nodes=4)
RATE = Rate(freq=10, per_ns=NANO)  # 10 tokens/s, capacity 10


class Clock:
    def __init__(self, now=1000 * NANO):
        self.now = now

    def __call__(self):
        return self.now


def mk_engine(**cfg):
    clock = Clock()
    eng = DeviceEngine(CFG, node_slot=0, clock=clock)
    if cfg:
        eng.configure_lifecycle(**cfg)
    return eng, clock


class TestGcSweep:
    def test_spent_bucket_is_not_reclaimed(self):
        eng, clock = mk_engine()
        try:
            eng.take("a", RATE, 3)
            eng.flush()
            assert eng.gc_sweep(force=True) == 0
            assert eng.directory.lookup("a") is not None
        finally:
            eng.stop()

    def test_refilled_bucket_reclaims_from_device_and_directory(self):
        eng, clock = mk_engine()
        try:
            eng.take("a", RATE, 3)
            eng.take("b", RATE, 10)
            eng.flush()
            clock.now += 10 * NANO  # full refill for both
            assert eng.gc_sweep(force=True) == 2
            assert len(eng.directory) == 0
            assert eng.directory.lookup("a") is None
            st = eng.lifecycle_stats()
            assert st["engine_gc_reclaimed"] == 2
            assert st["engine_gc_tombstones"] == 2
        finally:
            eng.stop()

    def test_idle_gate_holds_without_pressure(self):
        eng, clock = mk_engine(idle_ms=1000)
        try:
            eng.take("a", RATE, 1)
            eng.flush()
            clock.now += 10 * NANO
            eng.take("warm", RATE, 1)  # refreshes last_used at +10s
            eng.flush()
            # Un-forced sweep: "a" is idle AND full -> reclaimed; "warm"
            # was just touched -> kept even though it will refill later.
            assert eng.gc_sweep() == 1
            assert eng.directory.lookup("a") is None
            assert eng.directory.lookup("warm") is not None
        finally:
            eng.stop()

    def test_reclaim_is_observation_equivalent(self):
        """The soak gate's core law at unit scale: a GC'd engine and a
        no-GC engine produce IDENTICAL per-take outcomes over the same
        seeded schedule with refill gaps."""
        rng = np.random.default_rng(7)
        names = [f"u{i}" for i in range(12)]
        ops = []
        t = 1000 * NANO
        for _ in range(150):
            t += int(rng.integers(0, 3 * NANO))
            ops.append((names[int(rng.integers(0, len(names)))], t,
                        int(rng.integers(1, 4))))

        def run(gc: bool):
            clock = Clock()
            eng = DeviceEngine(CFG, node_slot=0, clock=clock)
            out = []
            try:
                for i, (name, now, count) in enumerate(ops):
                    clock.now = now
                    out.append(eng.take(name, RATE, count)[:2])
                    if gc and i % 10 == 9:
                        eng.flush()
                        eng.gc_sweep(force=True)
                eng.flush()
                return out, eng.lifecycle_stats()["engine_gc_reclaimed"]
            finally:
                eng.stop()

        res_gc, reclaimed = run(True)
        res_ref, _ = run(False)
        assert res_gc == res_ref
        assert reclaimed > 0, "schedule never exercised a reclaim"

    def test_hosted_bucket_reclaims_via_numpy_twin(self):
        eng, clock = mk_engine()
        try:
            eng.take("h", RATE, 2)  # fresh bind -> host-resident
            assert eng.hosted_buckets == 1
            clock.now += 5 * NANO
            assert eng.gc_sweep(force=True) == 1
            assert eng.hosted_buckets == 0
            assert eng.directory.lookup("h") is None
        finally:
            eng.stop()

    def test_free_list_compaction_reuses_lowest_rows(self):
        eng, clock = mk_engine()
        try:
            for i in range(8):
                eng.take(f"k{i}", RATE, 1)
            eng.flush()
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 8
            row, _ = eng.assign_row("fresh", clock.now)
            assert row == 0  # lowest reclaimed row hands out first
            assert eng.lifecycle_stats()["engine_gc_compactions"] >= 1
        finally:
            eng.stop()


class TestTombstoneConservation:
    def test_reseed_restores_own_lane_and_clock(self):
        eng, clock = mk_engine()
        try:
            eng.take("a", RATE, 3)
            eng.flush()
            created0 = int(
                eng.directory.created_ns[eng.directory.lookup("a")]
            )
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 1
            r, ok, created = eng.take("a", RATE, 1)
            assert (r, ok, created) == (9, True, True)
            eng.flush()
            row = eng.directory.lookup("a")
            assert int(eng.directory.created_ns[row]) == created0
            pn, el = eng.row_view(row)
            # Own lane resumed ABOVE the tombstone values: taken =
            # 3 (pre-GC) + 1 (new), added = the 3-token refill grant.
            assert int(pn[0, 1]) == 4 * NANO
            assert int(pn[0, 0]) == 3 * NANO
        finally:
            eng.stop()

    def test_stale_echo_cannot_erase_post_reclaim_spend(self):
        """THE conservation scenario (protocol model: the rejected
        'gc-drops-admitted-tokens' mutation is this test without the
        tombstone): reclaim, re-create, spend — then a peer echoes the
        OLD own-lane values back. The max-join must keep the new spend
        visible, i.e. the balance reflects it after the echo."""
        eng, clock = mk_engine()
        try:
            eng.take("a", RATE, 3)  # own lane taken=3
            eng.flush()
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 1
            # Re-create + spend 2: own taken lane resumes at 3+2 (plus
            # the forfeited/refill bookkeeping keeps balance = 10-2).
            r, ok, _ = eng.take("a", RATE, 2)
            assert (r, ok) == (8, True)
            eng.flush()
            # Stale echo: a peer still holds our OLD lane (a=0, t=3e9)
            # from before the reclaim, echoed back on slot 0's lane via
            # the lane trailer (exact PN values).
            eng.ingest_delta(
                wire.from_nanotokens(
                    "a", 10 * NANO, 3 * NANO, 0,
                    origin_slot=0, cap_nt=10 * NANO,
                    lane_added_nt=0, lane_taken_nt=3 * NANO,
                ),
                slot=0,
            )
            eng.flush()
            assert eng.tokens("a") == 8  # spend survived the echo
        finally:
            eng.stop()

    def test_replication_recreation_reseeds(self):
        """A bucket re-created by an incoming DELTA (not a take) also
        resumes from its tombstone."""
        eng, clock = mk_engine()
        try:
            eng.take("a", RATE, 3)
            eng.flush()
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 1
            # Peer lane delta re-creates the row.
            eng.ingest_delta(
                wire.from_nanotokens(
                    "a", 12 * NANO, 2 * NANO, 0,
                    origin_slot=2, cap_nt=10 * NANO,
                    lane_added_nt=2 * NANO, lane_taken_nt=2 * NANO,
                ),
                slot=2,
            )
            eng.flush()
            row = eng.directory.lookup("a")
            pn, _ = eng.row_view(row)
            assert int(pn[0, 1]) == 3 * NANO  # own lane reseeded
            assert int(pn[2, 1]) == 2 * NANO  # peer lane merged
        finally:
            eng.stop()


class TestRemoteLanesKept:
    """A partial replicated join can read full: a peer's lane with a
    positive balance lands before the lanes that spend it. GC must not
    reclaim such a row, or the acknowledged remote lane is lost and the
    later spend reads against a smaller join."""

    @staticmethod
    def _lane(eng, name, slot, added, taken):
        eng.ingest_delta(
            wire.from_nanotokens(
                name, 10 * NANO, added, taken, origin_slot=slot,
                cap_nt=10 * NANO, lane_added_nt=added, lane_taken_nt=taken,
            ),
            slot=slot,
        )
        eng.flush()

    @pytest.mark.parametrize("hosted", [False, True])
    def test_over_full_partial_join_is_kept(self, hosted):
        eng, clock = mk_engine()
        try:
            own = 0
            if hosted:
                eng.take("a", RATE, 1)  # fresh bind -> host-resident
                assert eng.hosted_buckets == 1
                own = 1
            self._lane(eng, "a", 1, 5 * NANO, 0)
            assert eng.tokens("a") == 15 - own  # over capacity: reads full
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 0
            self._lane(eng, "a", 2, 0, 8 * NANO)
            assert eng.tokens("a") == 7 - own  # the whole join
        finally:
            eng.stop()


class TestMemoryBudget:
    def test_hard_watermark_sheds_new_names_only(self):
        eng, clock = mk_engine(max_buckets=4, window_ms=0)
        try:
            for i in range(4):
                eng.take(f"u{i}", RATE, 5)
            with pytest.raises(OverloadedError):
                eng.take("new", RATE, 1)
            r, ok, _ = eng.take("u0", RATE, 1)
            assert ok and r == 4
            assert profiling.COUNTERS.get("gc_pressure_shed") >= 1
            assert eng.lifecycle_stats()["engine_gc_shed"] >= 1
        finally:
            eng.stop()

    def test_pressure_sweep_frees_before_shedding(self):
        eng, clock = mk_engine(max_buckets=4, window_ms=0)
        try:
            for i in range(4):
                eng.take(f"u{i}", RATE, 5)
            clock.now += 10 * NANO  # everything refills
            # Emergency sweep inside the admission path frees budget —
            # the new name is admitted, not shed.
            r, ok, created = eng.take("new", RATE, 1)
            assert (ok, created) == (True, True)
        finally:
            eng.stop()

    def test_batch_path_sheds_per_request(self):
        eng, clock = mk_engine(max_buckets=4, window_ms=0)
        try:
            for i in range(4):
                eng.take(f"u{i}", RATE, 5)
            res = eng.submit_takes_batch(
                ["u0", "brand-new", "u1"], [RATE] * 3, [1, 1, 1]
            )
            assert res is not None
            (t0, _), (t1, c1), (t2, _) = res
            t0.wait(5)
            t2.wait(5)
            assert t0.ok and t2.ok
            assert not t1.ok and t1.remaining == 0 and not c1
        finally:
            eng.stop()

    def test_byte_budget_accounting_and_sentinel_breach(self):
        eng, clock = mk_engine(bytes_budget=500, window_ms=0)
        try:
            # First bucket fits under 500 B; its row (device + directory
            # metadata) then crosses the byte watermark.
            eng.take("a", RATE, 5)
            assert eng.state_bytes_in_use() >= 500
            with pytest.raises(OverloadedError):
                eng.take("b", RATE, 1)
            breaches = slo.SENTINEL.check()
            assert "budget" in [b["kind"] for b in breaches]
            assert profiling.COUNTERS.get("slo_breaches") >= 1
        finally:
            eng.stop()

    def test_sentinel_unregisters_on_stop(self):
        eng, _ = mk_engine(max_buckets=2)
        eng.stop()
        assert slo.SENTINEL._budget_src is None


class TestMeshLifecycle:
    def test_mesh_engine_gc_reclaims_via_host_directory(self):
        from patrol_tpu.runtime.mesh_engine import MeshEngine

        clock = Clock()
        eng = MeshEngine(
            LimiterConfig(buckets=64, nodes=4), replicas=1,
            node_slot=0, clock=clock,
        )
        try:
            stats = eng.stats()
            assert stats["mesh_demotion"] == "unsupported"
            assert stats["mesh_gc"] == "host-directory"
            eng.take("m", RATE, 3)
            eng.flush()
            assert eng.gc_sweep(force=True) == 0  # spent: kept
            clock.now += 10 * NANO
            assert eng.gc_sweep(force=True) == 1  # refilled: reclaimed
            r, ok, _ = eng.take("m", RATE, 1)
            assert (r, ok) == (9, True)  # tombstone reconstruction
        finally:
            eng.stop()
