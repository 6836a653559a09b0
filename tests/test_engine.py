"""Device engine + directory + repo tests: microbatching, coalescing,
incast dedup, Repo-seam compatibility."""

import threading
import time

import pytest

from patrol_tpu.models.limiter import NANO, LimiterConfig
from patrol_tpu.ops.rate import Rate
from patrol_tpu.ops import wire
from patrol_tpu.runtime.bucket import Bucket
from patrol_tpu.runtime.directory import BucketDirectory, DirectoryFullError
from patrol_tpu.runtime.engine import DeviceEngine
from patrol_tpu.runtime.repo import TPURepo

CFG = LimiterConfig(buckets=64, nodes=4)
RATE = Rate(freq=10, per_ns=NANO)


class FakeClock:
    def __init__(self, start_ns: int = 0):
        self.now = start_ns

    def __call__(self) -> int:
        return self.now

    def advance(self, ns: int) -> None:
        self.now += ns


@pytest.fixture
def engine():
    eng = DeviceEngine(CFG, node_slot=0, clock=FakeClock())
    yield eng
    eng.stop()


class TestDirectory:
    def test_assign_and_lookup(self):
        d = BucketDirectory(4)
        row, created = d.assign("a", 100)
        assert created and d.lookup("a") == row
        row2, created2 = d.assign("a", 200)
        assert row2 == row and not created2
        assert d.created_ns[row] == 100  # creation stamp is stable

    def test_full_then_release(self):
        d = BucketDirectory(2)
        d.assign("a", 0)
        d.assign("b", 0)
        with pytest.raises(DirectoryFullError):
            d.assign("c", 0)
        d.release("a")
        row, created = d.assign("c", 0)
        assert created and d.lookup("c") == row

    def test_cap_base_first_nonzero_wins(self):
        d = BucketDirectory(4)
        row, _ = d.assign("a", 0)
        assert d.init_cap_base(row, 0) == 0
        assert d.init_cap_base(row, 5 * NANO) == 5 * NANO
        assert d.init_cap_base(row, 9 * NANO) == 5 * NANO

    def test_cap_base_many_first_nonzero_wins_on_dups(self):
        """Batched init must keep the single-call semantics: zero caps are
        no-ops and the FIRST nonzero occurrence wins for a row duplicated
        within one batch (numpy fancy-assign alone would be last-wins)."""
        import numpy as np

        d = BucketDirectory(4)
        r0, _ = d.assign("a", 0)
        r1, _ = d.assign("b", 0)
        d.init_cap_base_many(
            np.array([r0, r0, r1, r1]),
            np.array([0, 7 * NANO, 3 * NANO, 9 * NANO]),
        )
        assert d.cap_base_nt[r0] == 7 * NANO  # zero skipped, first nonzero
        assert d.cap_base_nt[r1] == 3 * NANO  # first of the dups


class TestEngine:
    def test_basic_take(self, engine):
        remaining, ok, created = engine.take("k", RATE, 1)
        assert ok and created and remaining == 9
        remaining, ok, created = engine.take("k", RATE, 4)
        assert ok and not created and remaining == 5

    def test_burst_then_reject(self, engine):
        for _ in range(10):
            _, ok, _ = engine.take("b", RATE, 1)
            assert ok
        remaining, ok, _ = engine.take("b", RATE, 1)
        assert not ok and remaining == 0

    def test_refill_with_injected_clock(self, engine):
        clock = engine.clock
        for _ in range(10):
            engine.take("r", RATE, 1)
        clock.advance(NANO)  # 1s at 10:1s ⇒ full refill of 10
        remaining, ok, _ = engine.take("r", RATE, 10)
        assert ok and remaining == 0

    def test_concurrent_hot_bucket_admits_exactly_capacity(self, engine):
        """64 threads race 1-token takes on a 10-token bucket: exactly 10
        succeed. This is the lock-free answer to the reference's per-bucket
        mutex (bucket.go:21): admission is decided algebraically in the
        coalesced kernel row."""
        results = []
        lock = threading.Lock()

        def worker():
            _, ok, _ = engine.take("hot", RATE, 1)
            with lock:
                results.append(ok)

        threads = [threading.Thread(target=worker) for _ in range(64)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sum(results) == 10

    def test_merge_then_take(self, engine):
        engine.take("m", RATE, 1)  # creates the bucket, takes 1 of 10
        engine.ingest_delta(
            wire.from_nanotokens("m", 0, 5 * NANO, 0, origin_slot=2), slot=2
        )
        engine.flush()
        # 10 - 1 - 5 = 4 available
        remaining, ok, _ = engine.take("m", RATE, 4)
        assert ok and remaining == 0
        _, ok, _ = engine.take("m", RATE, 1)
        assert not ok

    def test_snapshot_lanes(self, engine):
        engine.take("s", RATE, 3)
        engine.ingest_delta(
            wire.from_nanotokens("s", NANO, 2 * NANO, 7, origin_slot=1), slot=1
        )
        engine.flush()
        states = engine.snapshot("s")
        by_slot = {s.origin_slot: s for s in states}
        # Dual payload (ops/wire.py): the float header carries the aggregate
        # scalar view (capacity-included added, total taken — what reference
        # peers max-merge); the trailer carries the exact per-lane values.
        cap = 10 * NANO  # RATE = 10:1s
        for s in states:
            assert s.cap_nt == cap
            assert s.added_nt == cap + NANO  # cap + Σ lane grants (1 ingested)
            assert s.taken_nt == 5 * NANO  # 3 local + 2 ingested
        assert by_slot[0].lane_taken_nt == 3 * NANO
        assert by_slot[1].lane_added_nt == NANO
        assert by_slot[1].lane_taken_nt == 2 * NANO

    def test_broadcast_hook_and_zero_suppression(self):
        got = []
        eng = DeviceEngine(CFG, node_slot=0, clock=FakeClock(), on_broadcast=got.append)
        try:
            # A failed take that commits nothing must NOT broadcast: a
            # zero-state packet is the incast request marker (repo.go:78-90).
            _, ok, _ = eng.take("z", Rate(), 1)  # zero rate ⇒ reject
            assert not ok
            eng.flush()
            assert got == []
            _, ok, _ = eng.take("z2", RATE, 2)
            assert ok
            eng.flush()
            assert len(got) == 1
            st = got[0][0]
            assert st.name == "z2" and st.origin_slot == 0
            assert st.taken_nt == 2 * NANO
        finally:
            eng.stop()


class TestEviction:
    def test_release_zeroes_and_recycles(self, engine):
        engine.take("old", RATE, 7)
        row = engine.directory.lookup("old")
        assert engine.release_bucket("old")
        assert engine.directory.lookup("old") is None
        # The recycled row must come back clean for a new bucket.
        row2, created = engine.directory.assign("new", 0)
        assert created and row2 == row
        remaining, ok, _ = engine.take("new", RATE, 1)
        assert ok and remaining == 9  # fresh capacity, no leak from "old"

    def test_release_unknown(self, engine):
        assert not engine.release_bucket("nope")

    def test_keyspace_overflow_recycles_lru(self, engine):
        """VERDICT r1 item 3: keyspace = 4× pool through the take path with
        zero failures — the pool self-recycles via LRU eviction instead of
        erroring (the reference grows unboundedly, repo.go:200-207)."""
        clock = engine.clock
        for i in range(4 * CFG.buckets):
            remaining, ok, _ = engine.take(f"key-{i}", RATE, 1)
            assert ok and remaining == 9  # every key admits as a fresh bucket
            clock.advance(1)  # distinct LRU stamps
        assert len(engine.directory) <= CFG.buckets
        assert engine.evictions >= 3 * CFG.buckets - CFG.buckets  # recycled a lot

    def test_hot_survivor_keeps_state_across_evictions(self, engine):
        """A recently-used bucket must survive pool churn with its limit
        intact: LRU picks idle victims, not the hot key."""
        clock = engine.clock
        for _ in range(10):
            _, ok, _ = engine.take("hot", RATE, 1)
            assert ok
        _, ok, _ = engine.take("hot", RATE, 1)
        assert not ok  # drained
        # Flood 4× the pool in cold keys, touching "hot" between batches so
        # it is never the LRU victim.
        for i in range(4 * CFG.buckets):
            clock.advance(1)
            engine.take(f"cold-{i}", RATE, 1)
            if i % 16 == 0:
                _, ok, _ = engine.take("hot", RATE, 1)
                assert not ok  # still drained ⇒ state survived, no reset
        assert engine.evictions > 0
        _, ok, _ = engine.take("hot", RATE, 1)
        assert not ok

    def test_pinned_rows_are_never_victims(self):
        d = BucketDirectory(4)
        rows = {}
        for i, name in enumerate(["a", "b", "c", "d"]):
            rows[name], _ = d.assign(name, i, pin=(name in ("a", "b")))
        victims = d.pick_victims(4)
        assert sorted(int(v) for v in victims) == sorted([rows["c"], rows["d"]])
        assert d.lookup("a") is not None and d.lookup("b") is not None
        assert d.lookup("c") is None and d.lookup("d") is None
        d.recycle(victims)
        assert d.free_rows() == 2
        # all-pinned pool: nothing evictable
        assert d.pick_victims(4).size == 0

    def test_assign_many_is_atomic_on_full(self):
        d = BucketDirectory(4)
        d.assign("a", 0)
        d.assign("b", 0)
        with pytest.raises(DirectoryFullError):
            d.assign_many(["x", "y", "z"], 1, pin=True)
        # nothing partially assigned or pinned
        assert len(d) == 2 and d.pins.sum() == 0
        rows = d.assign_many(["x", "y"], 1, pin=True)
        assert len(d) == 4 and list(d.pins[rows]) == [1, 1]
        d.unpin_rows(rows)
        assert d.pins.sum() == 0

    def test_assign_many_dedupes_names(self):
        d = BucketDirectory(4)
        rows = d.assign_many(["k", "k", "j", "k"], 5, pin=True)
        assert rows[0] == rows[1] == rows[3] != rows[2]
        assert len(d) == 2
        assert d.pins[rows[0]] == 3 and d.pins[rows[2]] == 1

    def test_bulk_ingest_takes_vectorized_path(self, engine):
        """ingest_deltas_batch must land deltas identically to singles."""
        n = engine.config.nodes
        engine.ingest_deltas_batch(
            ["v", "v", "w"],
            [1, 2, 1],
            [2 * NANO, 3 * NANO, NANO],
            [NANO, 0, 0],
            [5, 7, 9],
        )
        engine.flush()
        by_slot = {s.origin_slot: s for s in engine.snapshot("v")}
        assert by_slot[1].lane_added_nt == 2 * NANO
        assert by_slot[1].lane_taken_nt == NANO
        assert by_slot[2].lane_added_nt == 3 * NANO
        # Header carries the aggregate scalars (cap 0: no local take yet).
        assert by_slot[1].added_nt == 5 * NANO and by_slot[1].taken_nt == NANO
        assert engine.snapshot("w")[0].lane_added_nt == NANO


class TestShutdownDrain:
    def test_stop_completes_multi_tick_backlog(self):
        """stop()'s graceful drain produces ticks AFTER the stop flag is
        set (deferred diverse-key tickets need extra ticks); the completer
        must run every one of them — an abandoned completion would hang
        its caller forever with the row pin leaked."""
        eng = DeviceEngine(CFG, node_slot=0, clock=lambda: 0)
        # Same bucket, many distinct rates: forces one tick per key.
        tickets = [
            eng.submit_take("drain", Rate(freq=100 + i, per_ns=NANO), 1)[0]
            for i in range(12)
        ]
        eng.stop()  # drains the backlog, then joins feeder + completer
        for t in tickets:
            assert t.wait(10), "caller hung across shutdown drain"
        assert eng.directory.pins.sum() == 0  # no leaked pins


class TestSubmitTakesBatch:
    def test_batch_matches_singles(self, engine):
        """submit_takes_batch must admit/deny identically to per-request
        submit_take, coalescing same-bucket takes into one tick group."""
        rates = [RATE] * 6
        res = engine.submit_takes_batch(
            ["bt", "bt", "bt", "other", "bt", "bt"], rates, [2, 2, 2, 1, 2, 2]
        )
        assert res is not None
        outcomes = []
        for t, _created in res:
            t.wait()
            outcomes.append((t.ok, t.remaining))
        # bucket "bt" cap 10: five count-2 takes admit exactly five... cap
        # 10 admits 5×2; all five succeed, draining to 0.
        bt = [o for i, o in enumerate(outcomes) if i != 3]
        assert [ok for ok, _ in bt] == [True] * 5
        assert bt[-1][1] == 0
        assert outcomes[3] == (True, 9)
        # And the bucket is now empty:
        _, ok, _ = engine.take("bt", RATE, 1)
        assert not ok

    def test_batch_created_flags_and_pool_spent(self):
        eng = DeviceEngine(LimiterConfig(buckets=2, nodes=4), node_slot=0, clock=lambda: 0)
        try:
            res = eng.submit_takes_batch(["x", "x", "y"], [RATE] * 3, [1] * 3)
            flags = [c for _, c in res]
            # Sequential parity: only the FIRST occurrence of each bucket
            # is the creating miss (submit_take twice → (True, False)).
            assert flags == [True, False, True]
            for t, _ in res:
                t.wait()
            # Pool of 2 spent and pinned ⇒ batch for a third name → None.
            eng.directory.assign("x", 0, pin=True)
            eng.directory.assign("y", 0, pin=True)
            assert eng.submit_takes_batch(["z"], [RATE], [1]) is None
            eng.directory.unpin_rows([eng.directory.lookup("x"), eng.directory.lookup("y")])
        finally:
            eng.stop()


class TestRateDiversity:
    """The _group_tickets starvation bound: a rate-diversity flood on one
    bucket cannot starve an already-queued ticket (FIFO per row), and
    every tick makes at least one key of progress per row."""

    def test_diverse_key_flood_cannot_overtake_earlier_ticket(self, engine):
        import threading

        done_order: list = []
        lock = threading.Lock()

        def track(tag, ticket):
            def record():
                with lock:
                    done_order.append(tag)

            ticket.add_done_callback(record)

        # Victim queued first, then a flood of 40 distinct-rate tickets on
        # the SAME bucket arriving after it.
        victim, _ = engine.submit_take("hotbkt", Rate(freq=100, per_ns=NANO), 1)
        track("victim", victim)
        flood = []
        for i in range(40):
            t, _ = engine.submit_take(
                "hotbkt", Rate(freq=200 + i, per_ns=NANO), 1
            )
            track(("flood", i), t)
            flood.append(t)
        assert victim.wait(30), "victim starved by diverse-key flood"
        for t in flood:
            assert t.wait(30), "flood ticket itself starved"
        # FIFO bound: the victim completed before every flood ticket.
        with lock:
            assert done_order[0] == "victim"

    def test_all_diverse_keys_complete_one_per_tick_bound(self, engine):
        t0 = engine.ticks
        tickets = [
            engine.submit_take("divbkt", Rate(freq=50 + i, per_ns=NANO), 1)[0]
            for i in range(16)
        ]
        for t in tickets:
            assert t.wait(30)
        # ≥1 key of progress per tick: 16 distinct keys cost ≤ 16 ticks
        # of same-row serialization (plus a bounded few for scheduling).
        assert engine.ticks - t0 <= 16 + 4


class TestIngestWireSemantics:
    """The mixed-cluster ingest contract (ops/wire.py): each sender class
    must route through the right merge path — exact lane values for lane
    trailers, raw lane for base (cap-less) trailers, deficit attribution
    for aggregate headers (with-cap trailers and v1 packets)."""

    def test_with_cap_only_routes_to_deficit_attribution(self, engine):
        """A with-cap trailer's header is the sender's AGGREGATE: merging
        it into the sender's lane directly would double-count every other
        lane's echoed grants. It must deficit-attribute with the wire cap."""
        engine.take("wc", RATE, 2)  # own lane: taken=2, cap_base=10
        cap = 10 * NANO
        # Peer (slot 1) echoes our 2 takes plus 2 of its own; its added
        # aggregate is cap + 3 grants.
        engine.ingest_delta(
            wire.from_nanotokens(
                "wc", cap + 3 * NANO, 4 * NANO, 0, origin_slot=1, cap_nt=cap
            ),
            slot=1,
        )
        engine.flush()
        by_slot = {s.origin_slot: s for s in engine.snapshot("wc")}
        assert by_slot[1].lane_added_nt == 3 * NANO  # header − wire cap
        assert by_slot[1].lane_taken_nt == 2 * NANO  # 4 − our echoed 2

    def test_v1_dropped_until_capacity_known_then_attributed(self, engine):
        """A v1 (reference) delta on a row with unknown capacity is dropped
        (the lazy-init cap can't be separated from grants); once a local
        take reveals the capacity, the rebroadcast lands."""
        v1 = wire.from_nanotokens("v1b", 13 * NANO, 4 * NANO, 0)
        engine.ingest_delta(v1, slot=1, scalar=True)
        engine.flush()
        assert engine.scalar_dropped == 1
        engine.take("v1b", RATE, 1)  # cap_base now 10; own taken=1
        engine.ingest_delta(v1, slot=1, scalar=True)  # the rebroadcast
        engine.flush()
        by_slot = {s.origin_slot: s for s in engine.snapshot("v1b")}
        assert by_slot[1].lane_added_nt == 3 * NANO  # 13 − our cap 10
        assert by_slot[1].lane_taken_nt == 3 * NANO  # 4 − our echoed 1

    def test_batch_classification_all_sender_classes(self, engine):
        """One vectorized batch mixing all four sender classes must land
        each delta through its own merge path."""
        cap = 10 * NANO
        engine.take("bv", RATE, 1)  # reveal capacity for the v1 delta
        engine.ingest_deltas_batch(
            ["bl", "bc", "bv", "bb"],
            [2, 2, 2, 2],
            [NANO, cap + 3 * NANO, cap + 3 * NANO, 0],
            [2 * NANO, 4 * NANO, 4 * NANO, 5 * NANO],
            [0, 0, 0, 0],
            caps_nt=[cap, cap, -1, -1],
            lane_added_nt=[NANO, -1, -1, -1],
            lane_taken_nt=[2 * NANO, -1, -1, -1],
            scalar=[False, False, True, False],
        )
        engine.flush()
        lane = {
            n: {s.origin_slot: s for s in engine.snapshot(n)}[2]
            for n in ("bl", "bc", "bv", "bb")
        }
        # Lane trailer: exact values (the header aggregate is ignored).
        assert lane["bl"].lane_added_nt == NANO
        assert lane["bl"].lane_taken_nt == 2 * NANO
        # With-cap trailer: deficit attribution with the WIRE cap (fresh
        # row, no other lanes ⇒ full header-minus-cap attributed).
        assert lane["bc"].lane_added_nt == 3 * NANO
        assert lane["bc"].lane_taken_nt == 4 * NANO
        # v1 packet: deficit attribution against our lane (taken 1).
        assert lane["bv"].lane_added_nt == 3 * NANO
        assert lane["bv"].lane_taken_nt == 3 * NANO
        # Base (cap-less) trailer: raw own-lane header, no cap subtraction.
        assert lane["bb"].lane_added_nt == 0
        assert lane["bb"].lane_taken_nt == 5 * NANO

    def test_batch_scalar_without_caps_matches_single_delta_path(self, engine):
        """scalar flags must be honored even without a caps array — parity
        with ingest_delta(state, slot, scalar=True)."""
        engine.take("nsc", RATE, 1)  # cap_base 10, own taken 1
        engine.ingest_deltas_batch(
            ["nsc"], [1], [13 * NANO], [4 * NANO], [0], scalar=[True]
        )
        engine.flush()
        by_slot = {s.origin_slot: s for s in engine.snapshot("nsc")}
        assert by_slot[1].lane_added_nt == 3 * NANO  # 13 − our cap 10
        assert by_slot[1].lane_taken_nt == 3 * NANO  # 4 − our echoed 1

    def test_lane_merges_apply_before_scalar_in_one_tick(self, engine):
        """A scalar echo's aggregate already includes peer lanes broadcast
        before it. If the deficit attribution ran before those lane deltas
        landed (they share a tick), the echoed grants would be attributed
        to the reference peer's lane AND merged into the patrol peer's lane
        — a permanent double count (lanes are monotone max)."""
        cap = 10 * NANO
        engine.take("ord", RATE, 1)  # own lane taken=1, cap known
        # Scalar delta FIRST in the batch: reference peer (slot 1) echoes
        # patrol peer slot 2's grant of 5 in its aggregate.
        engine.ingest_deltas_batch(
            ["ord", "ord"],
            [1, 2],
            [cap + 5 * NANO, cap + 5 * NANO],
            [NANO, NANO],
            [0, 0],
            caps_nt=[-1, cap],
            lane_added_nt=[-1, 5 * NANO],
            lane_taken_nt=[-1, 0],
            scalar=[True, False],
        )
        engine.flush()
        by_slot = {s.origin_slot: s for s in engine.snapshot("ord")}
        # Slot 2's lane lands first; the echo's 5 is then fully explained
        # by it ⇒ nothing attributed to slot 1.
        assert by_slot[2].lane_added_nt == 5 * NANO
        assert by_slot.get(1) is None or by_slot[1].lane_added_nt == 0
        total_added = sum(s.lane_added_nt for s in by_slot.values())
        assert total_added == 5 * NANO  # NOT 10: no double count


class TestTPURepo:
    def test_get_bucket_evicts_on_spent_pool(self, engine):
        """get_bucket must ride the same eviction path as the take path:
        a spent pool evicts an idle row instead of raising."""
        repo = TPURepo(engine)
        clock = engine.clock
        for i in range(CFG.buckets):
            engine.take(f"fill-{i}", RATE, 1)
            clock.advance(1)
        engine.flush()
        b, existed = repo.get_bucket("fresh-after-full")
        assert not existed and b.name == "fresh-after-full"
        assert len(engine.directory) <= CFG.buckets

    def test_incast_on_miss_once(self, engine):
        asked = []
        repo = TPURepo(engine, send_incast=asked.append, incast_ttl_s=10.0)
        repo.take("x", RATE, 1)
        repo.take("x", RATE, 1)
        repo.take("y", RATE, 1)
        assert asked == ["x", "y"]  # deduped within TTL (≙ singleflight)

    def test_get_bucket_view(self, engine):
        repo = TPURepo(engine)
        repo.take("v", RATE, 3)
        engine.flush()
        b, existed = repo.get_bucket("v")
        assert existed
        assert b.tokens() == 7
        assert b.created_ns == 0

    def test_get_bucket_creates(self, engine):
        repo = TPURepo(engine)
        b, existed = repo.get_bucket("fresh")
        assert not existed and b.is_zero()

    def test_upsert_merges(self, engine):
        repo = TPURepo(engine)
        incoming = Bucket(name="u", added_nt=10 * NANO, taken_nt=4 * NANO, elapsed_ns=5)
        view, existed = repo.upsert_bucket(incoming)
        assert not existed
        assert view.tokens() == 6

    def test_take_async(self, engine):
        import asyncio

        repo = TPURepo(engine)

        async def go():
            return await repo.take_async("a", RATE, 2)

        remaining, ok = asyncio.run(go())
        assert ok and remaining == 8


def _deltas(rows, slots, added, taken, elapsed, scalar=None):
    import numpy as np

    from patrol_tpu.runtime.engine import DeltaArrays

    rows = np.asarray(rows, np.int64)
    return DeltaArrays(
        rows=rows,
        slots=np.asarray(slots, np.int64),
        added_nt=np.asarray(added, np.int64),
        taken_nt=np.asarray(taken, np.int64),
        elapsed_ns=np.asarray(elapsed, np.int64),
        scalar=np.zeros(len(rows), bool) if scalar is None else np.asarray(scalar, bool),
    )


def _random_deltas(seed, n, rows_hi, nodes, hi=10**15):
    import numpy as np

    rng = np.random.default_rng(seed)
    return _deltas(
        rng.integers(0, rows_hi, n), rng.integers(0, nodes, n),
        rng.integers(0, hi, n), rng.integers(0, hi, n), rng.integers(0, hi, n),
    )


def _as_batch(d):
    import jax.numpy as jnp

    from patrol_tpu.ops.merge import MergeBatch

    return MergeBatch(
        rows=jnp.asarray(d.rows, jnp.int32),
        slots=jnp.asarray(d.slots, jnp.int32),
        added_nt=jnp.asarray(d.added_nt),
        taken_nt=jnp.asarray(d.taken_nt),
        elapsed_ns=jnp.asarray(d.elapsed_ns),
    )


def _merge_reference(state, d):
    """The sequential reference for one tick's deltas: the plain
    scatter-max join of the lane deltas, then the deficit-attribution
    merge of the scalar ones (the engine applies lanes first)."""
    from patrol_tpu.ops.merge import merge_batch, merge_scalar_batch
    from patrol_tpu.runtime.engine import DeltaArrays

    lanes = DeltaArrays(*(a[~d.scalar] for a in d))
    scalars = DeltaArrays(*(a[d.scalar] for a in d))
    if len(lanes):
        state = merge_batch(state, _as_batch(lanes))
    if len(scalars):
        state = merge_scalar_batch(state, _as_batch(scalars))
    return state


def _tick_cases():
    """case → (config, the ticks' deltas, the tick kernels they must reach)."""
    import numpy as np

    from patrol_tpu.runtime.engine import MAX_MERGE_ROWS

    folded, dense = "_jit_merge_folded", "_jit_merge_rows_dense"
    commit, scalar = "_jit_commit_packed", "_jit_merge_scalar_packed"
    big, small = (5 << 32) + 1, (4 << 32) + 0xFFFFFFFF
    over = np.arange(MAX_MERGE_ROWS + 1)
    wide = np.arange(3 * MAX_MERGE_ROWS + 5)
    rng = np.random.default_rng(12)
    hot = np.repeat([3, 7], 12)
    sparse = 10 + np.arange(200) // 2
    cases = {
        f"random-{seed}": (
            LimiterConfig(buckets=2048, nodes=8),
            [_random_deltas(seed, 300, 2048, 8)], {folded},
        )
        for seed in (1, 2, 3)
    }
    cases.update({
        "nonzero-state": (
            LimiterConfig(buckets=1024, nodes=4),
            [_random_deltas(9, 100, 1024, 4), _random_deltas(10, 150, 1024, 4)],
            {folded},
        ),
        "duplicate-row-slot": (
            LimiterConfig(buckets=512, nodes=4),
            [_deltas([5, 5, 5], [2, 2, 2], [9, 3, 7], [1, 8, 2], [4, 4, 6])],
            {folded},
        ),
        "beyond-2^32": (
            LimiterConfig(buckets=512, nodes=2),
            [_deltas([1, 1], [0, 0], [small, big], [big, small],
                     [2**40 + 3, 2**40 + 2])],
            {folded},
        ),
        "single-delta": (
            LimiterConfig(buckets=512, nodes=2),
            [_deltas([0], [1], [42], [0], [0])], {folded},
        ),
        "pad-straddle": (
            LimiterConfig(buckets=256, nodes=4),
            [_deltas(np.arange(17), np.ones(17), 7 + np.arange(17),
                     np.arange(17), np.full(17, NANO))],
            {folded},
        ),
        # A drain just over one block whose fold stays past it: the ring.
        "over-one-block": (
            LimiterConfig(buckets=4096, nodes=4),
            [_deltas(over % 4096, over // 4096, over + 1, over, over)],
            {commit},
        ),
        # A drain over one block that the fold collapses back into one.
        "over-one-block-collapses": (
            LimiterConfig(buckets=64, nodes=8),
            [_random_deltas(4, MAX_MERGE_ROWS + 1, 64, 8)], {folded},
        ),
        "multi-block": (
            LimiterConfig(buckets=8192, nodes=4),
            [_deltas(wide % 8192, wide // 8192, 3 * wide, wide, wide)],
            {commit},
        ),
        "hot-row-every-lane": (
            LimiterConfig(buckets=64, nodes=16),
            [_random_deltas(5, 256, 1, 16)], {dense},
        ),
        "dense-and-sparse": (
            LimiterConfig(buckets=256, nodes=12),
            [_deltas(
                np.concatenate([hot, sparse]),
                np.concatenate([np.tile(np.arange(12), 2), np.arange(200) % 2]),
                rng.integers(0, 1 << 50, 224), rng.integers(0, 1 << 50, 224),
                rng.integers(0, 1 << 50, 224),
            )],
            {dense, folded},
        ),
        # Past the native fold's batch floor: clustered rows, every lane.
        "clustered-native-fold": (
            LimiterConfig(buckets=128, nodes=16),
            [_random_deltas(6, 4096, 40, 16)], {dense},
        ),
        # More dense-eligible rows than the row-window batch holds: the
        # overflow rows ride the folded scatter.
        "dense-cap-spill": (
            LimiterConfig(buckets=1024, nodes=4),
            [_deltas(np.repeat(np.arange(600), 4), np.tile(np.arange(4), 600),
                     rng.integers(0, 1 << 50, 2400), rng.integers(0, 1 << 50, 2400),
                     rng.integers(0, 1 << 50, 2400))],
            {dense, folded},
        ),
        "empty-tick": (
            LimiterConfig(buckets=64, nodes=4),
            [_deltas([], [], [], [], [])], set(),
        ),
        "scalar-only": (
            LimiterConfig(buckets=64, nodes=4),
            [_deltas(np.arange(12), np.full(12, 2), np.full(12, 3 * NANO),
                     np.full(12, NANO), np.full(12, NANO), scalar=np.ones(12))],
            {scalar},
        ),
        "scalar-and-lane": (
            LimiterConfig(buckets=64, nodes=4),
            [_deltas(
                np.concatenate([rng.integers(0, 64, 50), np.arange(10)]),
                np.concatenate([rng.integers(0, 4, 50), np.ones(10)]),
                np.concatenate([rng.integers(0, 1 << 40, 50), np.full(10, 5 * NANO)]),
                np.concatenate([rng.integers(0, 1 << 40, 50), np.full(10, NANO)]),
                np.concatenate([rng.integers(0, 1 << 40, 50), np.full(10, NANO)]),
                scalar=np.arange(60) >= 50,
            )],
            {folded, scalar},
        ),
    })
    return cases


_TICK_CASES = _tick_cases()


class TestTickFold:
    """The tick-level merge fold (engine._fold_lane_merges): sorts by
    (row, slot), max-joins duplicate keys, folds elapsed per row, and pads
    with unique out-of-bounds sentinel keys the scatter drops — the
    preparation that lets the device scatter assert unique+sorted indices
    truthfully. Every backend's merge tick folds, so these run the tick
    the chip runs and hold it to the sequential ``merge_batch`` join."""

    @pytest.mark.parametrize(
        "case", [*_TICK_CASES, "promote-device", "promote-mesh"]
    )
    def test_tick_matches_merge_batch(self, case, monkeypatch):
        import numpy as np

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.runtime import engine as engine_mod

        if case.startswith("promote-"):
            self._promotion_matches_merge_batch(case == "promote-mesh")
            return
        cfg, ticks, kernels = _TICK_CASES[case]
        reached = set()
        for name in ("_jit_merge_folded", "_jit_merge_rows_dense",
                     "_jit_commit_packed", "_jit_merge_scalar_packed"):
            factory = getattr(engine_mod, name)

            def spy(factory=factory, name=name):
                reached.add(name)
                return factory()

            monkeypatch.setattr(engine_mod, name, spy)
        eng = DeviceEngine(cfg, node_slot=0)
        ref = init_state(cfg)
        try:
            for d in ticks:
                eng._apply(d, ())
                ref = _merge_reference(ref, d)
            assert reached == kernels
            assert np.array_equal(np.asarray(eng.state.pn), np.asarray(ref.pn))
            assert np.array_equal(
                np.asarray(eng.state.elapsed), np.asarray(ref.elapsed)
            )
        finally:
            eng.stop()

    @staticmethod
    def _promotion_matches_merge_batch(mesh: bool) -> None:
        """Host-resident rows promoted through the feeder's drain land
        in the device planes as the join of their lanes, on each engine."""
        import numpy as np

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.runtime.engine import HostLanes
        from patrol_tpu.runtime.mesh_engine import MeshEngine

        cfg = LimiterConfig(buckets=64, nodes=4)
        eng = (
            MeshEngine(cfg, replicas=2, node_slot=0)
            if mesh
            else DeviceEngine(cfg, node_slot=0)
        )
        rng = np.random.default_rng(21)
        rows = [3, 9, 17, 40, 63]
        lanes_of = {}
        for i, row in enumerate(rows):
            lanes = HostLanes(cfg.nodes)
            if i < 3:
                lanes.added[:] = rng.integers(0, 1 << 50, cfg.nodes)
                lanes.taken[:] = rng.integers(0, 1 << 50, cfg.nodes)
            lanes.elapsed_ns = 0 if i == 4 else int(rng.integers(1, 1 << 40))
            lanes_of[row] = lanes
        try:
            with eng._host_mu:
                for row, lanes in lanes_of.items():
                    eng._hosted[row] = lanes
                    eng._hosted_flag[row] = True
            assert eng.flush_hosted(timeout=60) == len(rows)
            ref = _merge_reference(init_state(cfg), _deltas(
                np.repeat(rows, cfg.nodes),
                np.tile(np.arange(cfg.nodes), len(rows)),
                np.concatenate([lanes_of[r].added for r in rows]),
                np.concatenate([lanes_of[r].taken for r in rows]),
                np.repeat([lanes_of[r].elapsed_ns for r in rows], cfg.nodes),
            ))
            assert np.array_equal(np.asarray(eng.state.pn), np.asarray(ref.pn))
            assert np.array_equal(
                np.asarray(eng.state.elapsed), np.asarray(ref.elapsed)
            )
            assert not eng._promoting and not eng._hosted_flag.any()
        finally:
            eng.stop()

    def test_fold_matches_unfolded_join(self):
        import numpy as np

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.ops.merge import (
            FoldedMergeBatch,
            MergeBatch,
            merge_batch,
            merge_batch_folded,
        )
        from patrol_tpu.runtime.engine import DeviceEngine, DeltaArrays

        rng = np.random.default_rng(42)
        n = 257  # odd, > one pow2 boundary
        rows = rng.integers(0, 64, n)
        slots = rng.integers(0, 8, n)
        deltas = DeltaArrays(
            rows=rows,
            slots=slots,
            added_nt=rng.integers(0, 1 << 50, n),
            taken_nt=rng.integers(0, 1 << 50, n),
            elapsed_ns=rng.integers(0, 1 << 50, n),
            scalar=np.zeros(n, bool),
        )
        packed = DeviceEngine._fold_lane_merges(deltas)
        cfg = LimiterConfig(buckets=64, nodes=8)

        import jax.numpy as jnp

        ref = merge_batch(
            init_state(cfg),
            MergeBatch(
                rows=jnp.asarray(rows, jnp.int32),
                slots=jnp.asarray(slots, jnp.int32),
                added_nt=jnp.asarray(deltas.added_nt),
                taken_nt=jnp.asarray(deltas.taken_nt),
                elapsed_ns=jnp.asarray(deltas.elapsed_ns),
            ),
        )
        got = merge_batch_folded(
            init_state(cfg),
            FoldedMergeBatch(
                rows=jnp.asarray(packed[0], jnp.int32),
                slots=jnp.asarray(packed[1], jnp.int32),
                added_nt=jnp.asarray(packed[2]),
                taken_nt=jnp.asarray(packed[3]),
                erows=jnp.asarray(packed[4], jnp.int32),
                elapsed_ns=jnp.asarray(packed[5]),
            ),
        )
        assert np.array_equal(np.asarray(ref.pn), np.asarray(got.pn))
        assert np.array_equal(np.asarray(ref.elapsed), np.asarray(got.elapsed))
        # Fold invariants the scatter flags rely on: keys strictly unique
        # and sorted ACROSS the whole matrix (padding included), with the
        # padding out of bounds so mode="drop" discards it.
        from patrol_tpu.runtime.engine import _FOLD_PAD_ROW

        key = packed[0] * 100000 + packed[1]
        assert (np.diff(key) > 0).all(), "(row, slot) keys not strictly sorted"
        live = packed[0] < _FOLD_PAD_ROW
        assert live.sum() == len(np.unique(np.stack([rows, slots]), axis=1).T)
        assert (packed[0][~live] >= 64).all(), "padding keys must be OOB"
        assert (np.diff(packed[4]) > 0).all(), "elapsed rows not strictly sorted"
        elive = packed[4] < _FOLD_PAD_ROW
        assert elive.sum() == len(np.unique(rows))
        assert (packed[4][~elive] >= 64).all()

    def test_fold_equivalence_randomized(self):
        """Multi-seed law check: for ANY batch (duplicates, hot keys,
        single-entry, pow2-straddling sizes), folded-prep + flagged kernel
        == plain scatter-max join."""
        import numpy as np

        import jax.numpy as jnp

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.ops.merge import (
            FoldedMergeBatch,
            MergeBatch,
            merge_batch,
            merge_batch_folded,
        )
        from patrol_tpu.runtime.engine import DeviceEngine, DeltaArrays

        cfg = LimiterConfig(buckets=16, nodes=4)
        for seed in range(8):
            rng = np.random.default_rng(seed)
            n = int(rng.integers(1, 70))
            rows = rng.integers(0, 4 if seed % 2 else 16, n)  # hot vs spread
            slots = rng.integers(0, 4, n)
            deltas = DeltaArrays(
                rows=rows,
                slots=slots,
                added_nt=rng.integers(0, 1 << 40, n),
                taken_nt=rng.integers(0, 1 << 40, n),
                elapsed_ns=rng.integers(0, 1 << 40, n),
                scalar=np.zeros(n, bool),
            )
            packed = DeviceEngine._fold_lane_merges(deltas)
            ref = merge_batch(
                init_state(cfg),
                MergeBatch(
                    rows=jnp.asarray(rows, jnp.int32),
                    slots=jnp.asarray(slots, jnp.int32),
                    added_nt=jnp.asarray(deltas.added_nt),
                    taken_nt=jnp.asarray(deltas.taken_nt),
                    elapsed_ns=jnp.asarray(deltas.elapsed_ns),
                ),
            )
            got = merge_batch_folded(
                init_state(cfg),
                FoldedMergeBatch(
                    rows=jnp.asarray(packed[0], jnp.int32),
                    slots=jnp.asarray(packed[1], jnp.int32),
                    added_nt=jnp.asarray(packed[2]),
                    taken_nt=jnp.asarray(packed[3]),
                    erows=jnp.asarray(packed[4], jnp.int32),
                    elapsed_ns=jnp.asarray(packed[5]),
                ),
            )
            assert np.array_equal(np.asarray(ref.pn), np.asarray(got.pn)), seed
            assert np.array_equal(
                np.asarray(ref.elapsed), np.asarray(got.elapsed)
            ), seed

    def test_native_fold_hybrid_matches_numpy(self, monkeypatch):
        """The C++ fold (pt_fold_hybrid) must be indistinguishable from
        the numpy fold-to-dense hybrid — same sparse pack, same dense
        row-window batch — over hot-key, clustered, and mixed shapes.
        The uniform shape must bail to numpy (identical by construction)."""
        import numpy as np

        from patrol_tpu import native as native_mod
        from patrol_tpu.runtime import engine as em
        from patrol_tpu.runtime.engine import DeltaArrays, fold_hybrid

        if native_mod.load() is None:
            pytest.skip("native toolchain unavailable")

        # Force multiple C++ shards so the shard-merge path (bitmap OR,
        # lane max, touched recompute) is exercised even on a 1-core box;
        # include a batch >65536 (the auto-threading scale) and a shape
        # with >MAX_ROW_DENSE dense-eligible rows (the dense-cap spill).
        monkeypatch.setenv("PATROL_FOLD_THREADS", "4")
        nodes = 64
        for seed, nrows, n, slot_hi in [
            (0, 1, 4396, 64), (1, 7, 4296, 64), (2, 300, 4196, 64),
            (3, 40, 4096, 64),
            (4, 16, 131072, 64),   # threading-scale batch, hot rows
            # >512 dense-eligible rows (25 touched slots ≥ dense_min 21):
            # exercises the dense-cap spill; the 188 spilled rows' pairs
            # stay under the pack's MAX_MERGE_ROWS tick contract.
            (5, 700, 131072, 25),
        ]:
            rng = np.random.default_rng(seed)
            rows = np.sort(rng.integers(0, nrows, n))
            deltas = DeltaArrays(
                rows=rows,
                slots=rng.integers(0, slot_hi, n),
                added_nt=rng.integers(0, 1 << 40, n),
                taken_nt=rng.integers(0, 1 << 40, n),
                elapsed_ns=rng.integers(0, 1 << 40, n),
                scalar=np.zeros(n, bool),
            )
            got = fold_hybrid(deltas, nodes, max(4, nodes // 3))
            monkeypatch.setattr(em, "_fold_hybrid_native", lambda *a: None)
            want = fold_hybrid(deltas, nodes, max(4, nodes // 3))
            monkeypatch.undo()
            g_packed, g_dense = got
            w_packed, w_dense = want
            if w_packed is None:
                assert g_packed is None, seed
            else:
                assert np.array_equal(g_packed, w_packed), seed
            if w_dense is None:
                assert g_dense is None, seed
            else:
                for gi, wi in zip(g_dense, w_dense):
                    assert np.array_equal(gi, wi), seed
        # Uniform shape: distinct rows past the bound must take the numpy
        # path (the native fold returns None internally) — same results
        # trivially; just pin that it doesn't crash or mis-shape.
        rng = np.random.default_rng(9)
        n = 8192
        deltas = DeltaArrays(
            rows=rng.integers(0, 1 << 20, n),
            slots=rng.integers(0, nodes, n),
            added_nt=rng.integers(0, 1 << 40, n),
            taken_nt=rng.integers(0, 1 << 40, n),
            elapsed_ns=rng.integers(0, 1 << 40, n),
            scalar=np.zeros(n, bool),
        )
        packed, dense = fold_hybrid(deltas, nodes, max(4, nodes // 3))
        assert packed is not None and dense is None

    def test_fold_empty_batch_is_noop(self):
        """A zero-length tick folds to an all-sentinel matrix whose merge
        leaves state untouched (ADVICE r3: the unfolded path handled n=0;
        the folded path must too — an IndexError here silently drops the
        whole tick via the tick loop's catch-all)."""
        import numpy as np

        import jax.numpy as jnp

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.ops.merge import FoldedMergeBatch, merge_batch_folded
        from patrol_tpu.runtime.engine import (
            _FOLD_PAD_ROW,
            DeltaArrays,
            DeviceEngine,
        )

        empty = DeltaArrays(
            rows=np.empty(0, np.int64),
            slots=np.empty(0, np.int64),
            added_nt=np.empty(0, np.int64),
            taken_nt=np.empty(0, np.int64),
            elapsed_ns=np.empty(0, np.int64),
            scalar=np.empty(0, bool),
        )
        packed = DeviceEngine._fold_lane_merges(empty)
        assert (packed[0] >= _FOLD_PAD_ROW).all()
        assert (packed[4] >= _FOLD_PAD_ROW).all()
        cfg = LimiterConfig(buckets=16, nodes=4)
        before = init_state(cfg)
        after = merge_batch_folded(
            before,
            FoldedMergeBatch(
                rows=jnp.asarray(packed[0], jnp.int32),
                slots=jnp.asarray(packed[1], jnp.int32),
                added_nt=jnp.asarray(packed[2]),
                taken_nt=jnp.asarray(packed[3]),
                erows=jnp.asarray(packed[4], jnp.int32),
                elapsed_ns=jnp.asarray(packed[5]),
            ),
        )
        assert np.array_equal(np.asarray(before.pn), np.asarray(after.pn))
        assert np.array_equal(
            np.asarray(before.elapsed), np.asarray(after.elapsed)
        )

    def test_engine_forced_fold_end_to_end(self):
        eng = DeviceEngine(LimiterConfig(buckets=32, nodes=4), node_slot=0)
        try:
            # Duplicate (row, slot) deltas across separate ingests land in
            # one tick often enough; either way the folded kernel applies.
            for v in (3, 7, 5):
                eng.ingest_delta(
                    wire.from_nanotokens(
                        "k", v * NANO, NANO, v, origin_slot=2,
                        cap_nt=10 * NANO, lane_added_nt=v * NANO,
                        lane_taken_nt=NANO,
                    ),
                    slot=2,
                )
            assert eng.flush(timeout=30)
            row = eng.directory.lookup("k")
            pn, el = eng.read_rows([row])
            assert int(pn[0][2, 0]) == 7 * NANO
            assert int(pn[0][2, 1]) == NANO
            assert int(el[0]) == 7
        finally:
            eng.stop()

    def test_folded_and_unfolded_engines_reach_identical_state(self):
        """A delta stream through the engine's folded tick reaches the
        state the unfolded sequential join reaches (``merge_batch`` per
        ingest batch, in order): the fold is pure batch preparation,
        never semantics."""
        import numpy as np

        from patrol_tpu.models.limiter import init_state

        rng = np.random.default_rng(9)
        streams = []
        for _ in range(6):  # several ingest batches → several ticks
            n = int(rng.integers(3, 40))
            streams.append(
                (
                    [f"b{int(rng.integers(0, 12))}" for _ in range(n)],
                    rng.integers(0, 4, n),
                    rng.integers(0, 1 << 40, n),
                    rng.integers(0, 1 << 40, n),
                    rng.integers(0, 1 << 40, n),
                )
            )

        cfg = LimiterConfig(buckets=32, nodes=4)
        eng = DeviceEngine(cfg, node_slot=0)
        try:
            for names, slots, a, t, e in streams:
                eng.ingest_deltas_batch(
                    names, slots.astype(np.int64), a.copy(), t.copy(), e.copy()
                )
            assert eng.flush(timeout=30)
            ref = init_state(cfg)
            for names, slots, a, t, e in streams:
                rows = [eng.directory.lookup(nm) for nm in names]
                ref = _merge_reference(ref, _deltas(rows, slots, a, t, e))
            live = [
                r for r in (eng.directory.lookup(f"b{i}") for i in range(12))
                if r is not None
            ]
            pn, el = eng.read_rows(live)
            assert np.array_equal(pn, np.asarray(ref.pn)[live])
            assert np.array_equal(el, np.asarray(ref.elapsed)[live])
        finally:
            eng.stop()


class TestScalarMergeChunking:
    def test_scalar_batch_past_pad_cap_chunks_instead_of_failing(self):
        """_pad_size clamps at MAX_MERGE_ROWS; a scalar (reference-peer)
        batch past it used to overflow its packed matrix (ValueError) and
        fail the whole tick. It must chunk — sequential application is
        exactly the reference's receive-loop semantics."""
        import numpy as np

        from patrol_tpu.runtime.engine import (
            MAX_MERGE_ROWS,
            DeltaArrays,
            DeviceEngine,
        )

        eng = DeviceEngine(LimiterConfig(buckets=16, nodes=4), node_slot=0)
        try:
            n = MAX_MERGE_ROWS + 123
            deltas = DeltaArrays(
                rows=np.arange(n, dtype=np.int64) % 16,
                slots=np.full(n, 1, np.int64),
                added_nt=np.full(n, NANO, np.int64),
                taken_nt=np.zeros(n, np.int64),
                elapsed_ns=np.full(n, NANO, np.int64),
                scalar=np.ones(n, bool),
            )
            eng._apply_scalar_merges(deltas)
            pn = np.asarray(eng.state.pn)
            assert (pn[:, 1, 0] > 0).all()  # every row's lane-1 got credit
        finally:
            eng.stop()


class TestFoldHybrid:
    """Fold-to-dense hybrid (VERDICT r3 item 3): rows touching many lanes
    commit as one full-row window; the split must join to exactly the
    plain scatter-max result for ANY batch."""

    def _commit(self, eng, packed, dense):
        import jax.numpy as jnp
        import numpy as np

        from patrol_tpu.ops.merge import (
            FoldedMergeBatch,
            RowDenseBatch,
            merge_batch_folded,
            merge_rows_dense,
        )

        state = eng  # LimiterState actually
        if dense is not None:
            rows_p, upd_p, el_p = dense
            state = merge_rows_dense(
                state,
                RowDenseBatch(
                    rows=jnp.asarray(rows_p, jnp.int32),
                    updates=jnp.asarray(upd_p),
                    elapsed_ns=jnp.asarray(el_p),
                ),
            )
        if packed is not None:
            state = merge_batch_folded(
                state,
                FoldedMergeBatch(
                    rows=jnp.asarray(packed[0], jnp.int32),
                    slots=jnp.asarray(packed[1], jnp.int32),
                    added_nt=jnp.asarray(packed[2]),
                    taken_nt=jnp.asarray(packed[3]),
                    erows=jnp.asarray(packed[4], jnp.int32),
                    elapsed_ns=jnp.asarray(packed[5]),
                ),
            )
        return state

    @pytest.mark.parametrize("shape", ["hotkey", "mixed", "uniform", "two-hot"])
    def test_hybrid_split_matches_plain_scatter(self, shape):
        import numpy as np

        import jax.numpy as jnp

        from patrol_tpu.models.limiter import init_state
        from patrol_tpu.ops.merge import MergeBatch, merge_batch
        from patrol_tpu.runtime.engine import DeltaArrays, DeviceEngine

        import zlib

        rng = np.random.default_rng(zlib.crc32(shape.encode()))
        cfg = LimiterConfig(buckets=64, nodes=16)
        n = 400
        if shape == "hotkey":
            rows = np.zeros(n, np.int64)
        elif shape == "two-hot":
            rows = rng.integers(0, 2, n)
        elif shape == "mixed":
            rows = np.where(rng.random(n) < 0.5, 3, rng.integers(0, 64, n))
        else:
            rows = rng.integers(0, 64, n)
        deltas = DeltaArrays(
            rows=rows,
            slots=rng.integers(0, 16, n),
            added_nt=rng.integers(0, 1 << 50, n),
            taken_nt=rng.integers(0, 1 << 50, n),
            elapsed_ns=rng.integers(0, 1 << 50, n),
            scalar=np.zeros(n, bool),
        )
        eng = DeviceEngine(cfg, node_slot=0)
        try:
            packed, dense = eng._fold_hybrid(deltas)
        finally:
            eng.stop()
        if shape in ("hotkey", "two-hot"):
            assert dense is not None, "hot rows must take the dense path"
        ref = merge_batch(
            init_state(cfg),
            MergeBatch(
                rows=jnp.asarray(rows, jnp.int32),
                slots=jnp.asarray(deltas.slots, jnp.int32),
                added_nt=jnp.asarray(deltas.added_nt),
                taken_nt=jnp.asarray(deltas.taken_nt),
                elapsed_ns=jnp.asarray(deltas.elapsed_ns),
            ),
        )
        got = self._commit(init_state(cfg), packed, dense)
        assert np.array_equal(np.asarray(ref.pn), np.asarray(got.pn)), shape
        assert np.array_equal(
            np.asarray(ref.elapsed), np.asarray(got.elapsed)
        ), shape

    def test_engine_tick_with_forced_fold_uses_hybrid(self):
        """End-to-end through _apply_lane_merges: a hot-key tick must
        land correctly."""
        import numpy as np

        from patrol_tpu.runtime.engine import DeltaArrays

        eng = DeviceEngine(LimiterConfig(buckets=32, nodes=8), node_slot=0)
        try:
            n = 256
            rng = np.random.default_rng(3)
            deltas = DeltaArrays(
                rows=np.zeros(n, np.int64),
                slots=rng.integers(0, 8, n),
                added_nt=rng.integers(0, 1 << 40, n),
                taken_nt=np.zeros(n, np.int64),
                elapsed_ns=rng.integers(0, 1 << 40, n),
                scalar=np.zeros(n, bool),
            )
            eng._apply_lane_merges(deltas)
            pn = np.asarray(eng.state.pn)
            for s in range(8):
                sel = deltas.slots == s
                if sel.any():
                    assert int(pn[0, s, 0]) == int(deltas.added_nt[sel].max())
        finally:
            eng.stop()


class _SpyHist:
    """A stage histogram's stand-in that keeps each record call's
    samples: one ``record_n`` call per tick."""

    def __init__(self):
        self.calls = []

    def record(self, value):
        self.calls.append([int(value)])

    def record_many(self, values):
        self.calls.append([int(v) for v in values])

    def record_n(self, value, n):
        self.calls.append([int(value)] * n)

    @property
    def samples(self):
        return [v for call in self.calls for v in call]


STAGES = ("TAKE_PREP", "TAKE_LOCK_WAIT", "TAKE_DISPATCH", "TAKE_HANDOFF", "TAKE_READBACK")


@pytest.fixture
def staged(monkeypatch):
    """An engine on an injected clock whose takes all ride the device
    queue, with spies for take_service_ns, take_queue_ns and the five
    take stages; → (engine, {attr: spy})."""
    from patrol_tpu.runtime import engine as engine_mod
    from patrol_tpu.utils import histogram as hist

    monkeypatch.setattr(engine_mod, "HOST_FASTPATH", False)
    spies = {a: _SpyHist() for a in ("TAKE_SERVICE", "TAKE_QUEUE") + STAGES}
    for attr, spy in spies.items():
        monkeypatch.setattr(hist, attr, spy)
    eng = DeviceEngine(CFG, node_slot=0, clock=FakeClock())
    yield eng, spies
    eng.stop()


def _paused(eng, submit):
    """Run ``submit`` with the feeder paused, so one drain takes all it
    queues; → what it returned."""
    with eng._cond:
        eng._tick_paused = True
    try:
        return submit()
    finally:
        with eng._cond:
            eng._tick_paused = False
            eng._cond.notify_all()


class TestTakeStages:
    """A device take ticket's take_service_ns tiled on the feeder and the
    completer: take_queue_ns (ticket → drain), then prep, lock wait,
    dispatch, hand-off and readback (drain → complete)."""

    def test_six_stages_sum_to_each_tickets_service_exactly(self, staged):
        eng, spies = staged
        tickets = []
        for _ in range(3):  # three ticks of many tickets, one a group of 3
            names = [f"tile{i}" for i in range(9)] + ["tile0", "tile0"]
            res = _paused(
                eng, lambda: eng.submit_takes_batch(names, [RATE] * 11, [1] * 11)
            )
            tickets += [t for t, _ in res]
            for t in tickets:
                assert t.wait(10)
        for i in range(4):  # and four ticks of one ticket
            tickets.append(eng.submit_take(f"solo{i}", RATE, 1)[0])
            assert tickets[-1].wait(10)
        assert eng.flush()
        n = len(tickets)
        for attr in ("TAKE_SERVICE", "TAKE_QUEUE") + STAGES:
            assert len(spies[attr].samples) == n, attr
        # Per tick (ordered by its drain stamp), every ticket's five
        # stages sum to drain → complete, and the queue wait before it
        # makes the ticket's take_service_ns sample.
        drains = sorted({t.drain_ns for t in tickets})
        assert 0 not in drains and len(drains) == 7
        for attr in STAGES:
            assert len(spies[attr].calls) == len(drains), attr
        for k, d in enumerate(drains):
            mine = [t for t in tickets if t.drain_ns == d]
            stages = [spies[a].calls[k] for a in STAGES]
            assert all(len(s) == len(mine) for s in stages)
            for i, t in enumerate(mine):
                per = [s[i] for s in stages]
                assert all(v >= 0 for v in per), per
                assert sum(per) == t.done_ns - t.drain_ns
        services = sorted(spies["TAKE_SERVICE"].samples)
        assert services == sorted(t.done_ns - t.t0_ns for t in tickets)
        assert sorted(spies["TAKE_QUEUE"].samples) == sorted(
            t.drain_ns - t.t0_ns for t in tickets
        )
        tiled = sum(sum(spies[a].samples) for a in ("TAKE_QUEUE",) + STAGES)
        assert tiled == sum(services)

    def test_state_lock_held_elsewhere_is_lock_wait(self, staged):
        eng, spies = staged
        eng.take("warm", RATE, 1)  # compile the take tick first
        real = eng._state_mu

        class Watched:
            """The state lock, telling when the feeder asks for it."""

            def __init__(self):
                self.asked = threading.Event()

            def acquire(self, *a):
                if threading.current_thread().name == "patrol-engine":
                    self.asked.set()
                return real.acquire(*a)

            def release(self):
                real.release()

            def __enter__(self):
                self.acquire()

            def __exit__(self, *exc):
                self.release()

        watched = eng._state_mu = Watched()
        held = threading.Event()

        def holder():
            with real:
                held.set()
                assert watched.asked.wait(10)
                time.sleep(0.05)

        th = threading.Thread(target=holder)
        th.start()
        try:
            assert held.wait(10)
            ticket, _ = eng.submit_take("held", RATE, 1)
            assert ticket.wait(10)
        finally:
            th.join(10)
        assert eng.flush()  # the stages are recorded after the tickets complete
        waits = spies["TAKE_LOCK_WAIT"].calls[-1]
        assert waits[0] >= 50_000_000
        stages = sum(spies[a].calls[-1][0] for a in STAGES)
        assert stages == ticket.done_ns - ticket.drain_ns
        assert spies["TAKE_QUEUE"].samples[-1] + stages == (
            spies["TAKE_SERVICE"].samples[-1]
        )

    def test_deferred_and_rerouted_tickets_record_no_stage(
        self, staged, monkeypatch
    ):
        from patrol_tpu.runtime import engine as engine_mod

        eng, spies = staged
        # Two rates on one bucket in one drain: the second defers to the
        # next tick, whose drain is its second.
        first, later = _paused(
            eng,
            lambda: [
                eng.submit_take("defer", RATE, 1)[0],
                eng.submit_take("defer", Rate(freq=20, per_ns=NANO), 1)[0],
            ],
        )
        assert first.wait(10) and later.wait(10)
        assert eng.flush()
        assert first.drain_ns and not later.drain_ns
        assert len(spies["TAKE_SERVICE"].samples) == 2
        assert len(spies["TAKE_QUEUE"].samples) == 2  # both drained once
        for attr in STAGES:
            assert len(spies[attr].samples) == 1, attr
        # Queued for the device, then its row turns host-resident before
        # the drain: the feeder serves it from the host model.

        def reroute():
            t = eng.submit_take("reroute", RATE, 1)[0]
            row = eng.directory.lookup("reroute")
            with eng._host_mu:
                eng._hosted[row] = engine_mod.HostLanes(eng.config.nodes)
                eng._hosted_flag[row] = True
            monkeypatch.setattr(engine_mod, "HOST_FASTPATH", True)
            return t

        ticket = _paused(eng, reroute)
        assert ticket.wait(10) and ticket.ok
        assert eng.flush()
        assert eng.host_takes == 1
        assert len(spies["TAKE_SERVICE"].samples) == 3
        assert len(spies["TAKE_QUEUE"].samples) == 3
        for attr in STAGES:
            assert len(spies[attr].samples) == 1, attr
