"""Device-resident ingest (ops/ingest.py + engine.ingest_raw_planes +
net/delta.py raw path): the differential sweep pinning the raw-plane
decode+fold against the Python wire decoder over the hostile corpus —
bit-exact VERDICTS and bit-exact FOLDED STATE — plus the host-walk parity, the engine seam (directory
pass, host-lane split via the kernel's hosted-mask output, tombstone
re-seed, release contract), the DeltaPlane raw path's counter parity
with the python decode path, the zero-copy rx ring, and the adaptive
commit-block governor.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from patrol_tpu import native
from patrol_tpu.models.limiter import NANO, LimiterConfig, init_state
from patrol_tpu.ops import ingest as ingest_ops
from patrol_tpu.ops import wire
from patrol_tpu.ops.rate import Rate
from patrol_tpu.runtime.engine import DeviceEngine
from patrol_tpu.runtime.repo import TPURepo
from patrol_tpu.utils import profiling

ROW = 2048
E = ingest_ops.max_entries(ROW)
RATE = Rate(freq=100, per_ns=3600 * NANO)


def mk_packet(seed, n_entries, name_pool=200, slot_max=4, seq=None,
              acks=(), big_values=False):
    r = np.random.default_rng(seed)
    hi = (1 << 62) if big_values else (1 << 50)
    ents = [
        wire.DeltaEntry(
            f"bkt{int(r.integers(0, name_pool))}",
            int(r.integers(0, slot_max)),
            int(r.integers(0, hi)),
            int(r.integers(0, hi)),
            int(r.integers(0, hi)),
            int(r.integers(0, hi)),
        )
        for _ in range(n_entries)
    ]
    data, n = wire.encode_delta_packet(
        3, int(r.integers(1, 1 << 32)) if seq is None else seq,
        list(acks), ents, max_size=ROW,
    )
    assert n == n_entries
    return data


def hostile_corpus(seed=20260805, n=80):
    """Mixed valid/invalid datagrams in one plane batch: truncations,
    single-byte flips, trailing garbage, bit-63 (hostile) values, random
    blobs, empty/zero-length names — the codec fuzz corpus shape."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        kind = i % 8
        b = bytearray(
            mk_packet(
                1000 + i, int(rng.integers(0, 40)),
                acks=[int(x) for x in rng.integers(0, 1 << 32, int(rng.integers(0, 6)))],
                big_values=(kind == 5),
            )
        )
        if kind == 1:
            b[int(rng.integers(0, len(b)))] ^= 0x41  # flip
        elif kind == 2:
            b = b[: int(rng.integers(1, len(b)))]  # truncate
        elif kind == 3:
            b += bytes(rng.integers(0, 256, int(rng.integers(1, 6))).astype(np.uint8))
        elif kind == 4:
            b = bytearray(rng.integers(0, 256, int(rng.integers(1, 300))).astype(np.uint8))
        elif kind == 6:
            # bit-63 value with a FIXED-UP checksum: only the value guard
            # can reject it.
            off = 32 + 8 + 4 * b[39] + 2
            off += 1 + b[off] + 2  # name_len + name + slot
            if off + 8 < len(b):
                b[off] |= 0x80
                b[-1] = sum(b[32:-1]) & 0xFF
        out.append(bytes(b))
    return out


def planes_of(raw, stale=0xAB):
    P = len(raw)
    planes = np.full((P, ROW), stale, np.uint8)  # stale ring bytes
    lengths = np.zeros(P, np.int32)
    for i, b in enumerate(raw):
        planes[i, : len(b)] = np.frombuffer(b, np.uint8)
        lengths[i] = min(len(b), ROW)
    return planes, lengths


def _native_lib():
    lib = native.load()
    if lib is None:
        pytest.skip("native toolchain unavailable: the C++ walk is not tested")
    return lib


@pytest.fixture(params=["numpy", "native"])
def walk_fn(request):
    """The two structure walks: the numpy reference and the C++ twin."""
    if request.param == "numpy":
        return ingest_ops.host_walk
    lib = _native_lib()
    return lambda planes, lengths: ingest_ops._native_walk(lib, planes, lengths)


def _padded(raw, pad):
    """A recv batch padded as the rx loop pads it: ``pad`` rows of zero
    length whose stale bytes still hold whole datagrams."""
    planes, lengths = planes_of(raw)
    return (
        np.concatenate([planes, planes[:pad]]),
        np.concatenate([lengths, np.zeros(pad, np.int32)]),
    )


def _full_row():
    """One 8-KiB datagram carrying the most entries a row can (232, with
    empty names), beside the same bytes with a length one entry short and
    a length past the row."""
    row = wire.DELTA_PACKET_SIZE
    ents = [wire.DeltaEntry("", i % 3, i, 2 * i, 3 * i, 4 * i) for i in range(232)]
    data, n = wire.encode_delta_packet(2, 77, [5, 6], ents, max_size=row)
    assert n == ingest_ops.max_entries(row) == 232
    planes = np.zeros((3, row), np.uint8)
    planes[:] = np.frombuffer(data.ljust(row, b"\xee"), np.uint8)
    lengths = np.array([len(data), len(data) - 35, row + 1], np.int64)
    return planes, lengths


WALK_CASES = {
    "corpus-20260805": lambda: planes_of(hostile_corpus()),
    "corpus-7": lambda: planes_of(hostile_corpus(seed=7, n=64)),
    "corpus-99": lambda: planes_of(hostile_corpus(seed=99, n=64)),
    "padded": lambda: _padded(hostile_corpus(seed=3, n=40), 24),
    "P1": lambda: planes_of([mk_packet(1, 30, acks=(4, 5))]),
    "P512": lambda: planes_of(hostile_corpus(seed=512, n=512)),
    "232-entries": _full_row,
}


class TestHostWalkParity:
    def test_verdicts_and_fields_match_python_decoder(self, walk_fn):
        raw = hostile_corpus()
        planes, lengths = planes_of(raw)
        walk = walk_fn(planes, lengths)
        for i, b in enumerate(raw):
            pk = wire.decode_delta_packet(b[:ROW] if len(b) > ROW else b)
            assert walk.ok[i] == (pk is not None), i
            if pk is None:
                assert walk.count[i] == 0
                continue
            assert walk.sender_slot[i] == pk.sender_slot
            assert walk.seq[i] == pk.seq
            assert tuple(walk.acks[i, : walk.n_acks[i]]) == pk.acks
            assert walk.count[i] == len(pk.entries)
            for j, e in enumerate(pk.entries):
                assert walk.slot[i, j] == e.slot
                assert walk.cap[i, j] == e.cap_nt
                assert walk.added[i, j] == e.added_nt
                assert walk.taken[i, j] == e.taken_nt
                assert walk.elapsed[i, j] == e.elapsed_ns
                nb = planes[
                    i, walk.name_off[i, j] : walk.name_off[i, j] + walk.name_len[i, j]
                ].tobytes()
                assert nb.decode("utf-8", "surrogateescape") == e.name

    @pytest.mark.parametrize("case", sorted(WALK_CASES))
    def test_native_walk_equals_host_walk(self, case):
        """Every RawWalk field of the C++ walk, element by element, dtype
        and shape, against the numpy reference — refused packets'
        framing proposals and zeroed values included."""
        lib = _native_lib()
        planes, lengths = WALK_CASES[case]()
        want = ingest_ops.host_walk(planes, lengths)
        got = ingest_ops._native_walk(lib, planes, lengths)
        assert got is not None
        for field in ingest_ops.RawWalk._fields:
            a, b = getattr(want, field), getattr(got, field)
            assert (a.dtype, a.shape) == (b.dtype, b.shape), field
            assert np.array_equal(a, b), (case, field, np.argwhere(a != b)[:4])
        if case == "232-entries":
            assert got.ok.tolist() == [True, False, False]
            assert int(got.count[0]) == 232

    def test_native_walk_refuses_mismatched_lengths(self):
        lib = _native_lib()
        planes, lengths = planes_of(hostile_corpus(seed=2, n=4))
        with pytest.raises(ValueError):
            ingest_ops._native_walk(lib, planes, lengths[:3])

    def test_walk_counts_the_path_it_took(self, monkeypatch):
        from patrol_tpu import native as native_mod

        _native_lib()
        planes, lengths = planes_of(hostile_corpus(seed=5, n=16))
        keys = ("rx_walk_native_batches", "rx_walk_numpy_batches", "rx_walk_ns")
        before = {k: profiling.COUNTERS.get(k) for k in keys}
        native_walk = ingest_ops.walk(planes, lengths)
        mid = {k: profiling.COUNTERS.get(k) for k in keys}
        monkeypatch.setattr(native_mod, "load", lambda: None)
        numpy_walk = ingest_ops.walk(planes, lengths)
        after = {k: profiling.COUNTERS.get(k) for k in keys}
        assert mid["rx_walk_native_batches"] == before["rx_walk_native_batches"] + 1
        assert mid["rx_walk_numpy_batches"] == before["rx_walk_numpy_batches"]
        assert after["rx_walk_numpy_batches"] == mid["rx_walk_numpy_batches"] + 1
        assert after["rx_walk_native_batches"] == mid["rx_walk_native_batches"]
        assert before["rx_walk_ns"] < mid["rx_walk_ns"] < after["rx_walk_ns"]
        for field in ingest_ops.RawWalk._fields:
            assert np.array_equal(
                getattr(native_walk, field), getattr(numpy_walk, field)
            ), field

    def test_dv2_mask_matches_is_delta_packet(self):
        raw = hostile_corpus(seed=7, n=40) + [b"", b"\x00" * 31, b"\x00" * 40]
        planes, lengths = planes_of(raw)
        m = ingest_ops.dv2_mask(planes, lengths)
        for i, b in enumerate(raw):
            assert m[i] == wire.is_delta_packet(b[:ROW]), i


def _reference_fold(raw, buckets, nodes, name_rows):
    pn = np.zeros((buckets, nodes, 2), np.int64)
    el = np.zeros(buckets, np.int64)
    for b in raw:
        pk = wire.decode_delta_packet(b[:ROW] if len(b) > ROW else b)
        if pk is None:
            continue
        for e in pk.entries:
            if e.slot >= nodes:
                continue
            r = name_rows.setdefault(e.name, len(name_rows))
            pn[r, e.slot, 0] = max(pn[r, e.slot, 0], e.added_nt)
            pn[r, e.slot, 1] = max(pn[r, e.slot, 1], e.taken_nt)
            el[r] = max(el[r], max(e.elapsed_ns, 0))
    return pn, el


class TestDecodeFoldDifferential:
    """The satellite sweep: device decode vs the Python decoder over the
    corpus — bit-exact verdicts AND folded state."""

    def test_corpus_bit_exact(self):
        raw = hostile_corpus()
        planes, lengths = planes_of(raw)
        P = len(raw)
        buckets, nodes = 256, 4
        name_rows: dict = {}
        ref_pn, ref_el = _reference_fold(raw, buckets, nodes, name_rows)
        rows = np.full((P, E), 10**9, np.int32)
        for i, b in enumerate(raw):
            pk = wire.decode_delta_packet(b[:ROW] if len(b) > ROW else b)
            if pk is None:
                continue
            for j, e in enumerate(pk.entries):
                rows[i, j] = name_rows.get(e.name, 10**9)
        hosted = np.zeros((P, E), bool)
        eoff = np.maximum(
            ingest_ops.host_walk(planes, lengths).name_off - 1, 0
        )
        st = init_state(LimiterConfig(buckets=buckets, nodes=nodes))
        args = (
            st, jnp.asarray(planes), jnp.asarray(lengths),
            jnp.asarray(eoff), jnp.asarray(rows), jnp.asarray(hosted),
        )
        out = ingest_ops.decode_fold_raw_jit(*args)
        state2, ok = out[0], np.asarray(out[1])
        want_ok = np.array(
            [wire.decode_delta_packet(b[:ROW] if len(b) > ROW else b) is not None for b in raw]
        )
        assert np.array_equal(ok, want_ok)
        assert np.array_equal(np.asarray(state2.pn), ref_pn)
        assert np.array_equal(np.asarray(state2.elapsed), ref_el)


class TestEngineRawSeam:
    """engine.ingest_raw_planes ≡ the python decode + ingest_interval
    path, end-to-end: directory pass, cap adoption, host-lane split via
    the kernel's hosted-mask output, fold, release contract."""

    def _mk_engine(self):
        return DeviceEngine(
            LimiterConfig(buckets=128, nodes=4), node_slot=0,
            clock=lambda: NANO,
        )

    def _feed_python(self, eng, raw):
        for b in raw:
            pk = wire.decode_delta_packet(b)
            if pk is None or not pk.entries:
                continue
            ents = [e for e in pk.entries if e.slot < 4]
            eng.ingest_interval(
                [e.name for e in ents],
                [e.slot for e in ents],
                [e.cap_nt for e in ents],
                [e.added_nt for e in ents],
                [e.taken_nt for e in ents],
                [e.elapsed_ns for e in ents],
            )

    def _feed_raw(self, eng, raw):
        planes, lengths = planes_of(raw)
        released = []
        n = eng.ingest_raw_planes(
            planes, lengths, release=lambda: released.append(1)
        )
        assert eng.flush(timeout=30)
        assert released == [1], "release must run exactly once"
        return n

    def _snapshot(self, eng, names):
        out = {}
        for nm in names:
            row = eng.directory.lookup(nm)
            if row is None:
                continue
            pn, el = eng.row_view(row)
            out[nm] = (pn.copy(), int(el))
        return out

    def test_raw_equals_python_path(self):
        raw = [mk_packet(i, 30, name_pool=40) for i in range(12)]
        raw += hostile_corpus(seed=3, n=16)  # invalid riders change nothing
        names = {
            e.name
            for b in raw
            if (pk := wire.decode_delta_packet(b)) is not None
            for e in pk.entries
        }
        e1, e2 = self._mk_engine(), self._mk_engine()
        try:
            before = profiling.COUNTERS.get("ingest_raw_device_dispatches")
            self._feed_raw(e1, raw)
            assert (
                profiling.COUNTERS.get("ingest_raw_device_dispatches") > before
            )
            self._feed_python(e2, raw)
            assert e2.flush(timeout=30)
            s1 = self._snapshot(e1, names)
            s2 = self._snapshot(e2, names)
            assert set(s1) == set(s2) == names
            for nm in names:
                assert np.array_equal(s1[nm][0], s2[nm][0]), nm
                assert s1[nm][1] == s2[nm][1], nm
            # Cap adoption rode the raw path too.
            for nm in list(names)[:8]:
                r1, r2 = e1.directory.lookup(nm), e2.directory.lookup(nm)
                assert (
                    e1.directory.cap_base_nt[r1] == e2.directory.cap_base_nt[r2]
                )
        finally:
            e1.stop()
            e2.stop()

    def test_hosted_rows_absorb_via_kernel_mask(self):
        """A host-resident bucket's entries route through the host-lane
        join (the kernel's hosted-mask output), never the device fold —
        and the merged view equals the python path's."""
        e1, e2 = self._mk_engine(), self._mk_engine()
        try:
            for eng in (e1, e2):
                repo = TPURepo(eng, send_incast=None)
                assert repo.take("hotbkt", RATE, 1)[1]  # host-resident now
                assert eng.flush(timeout=30)
            ents = [
                wire.DeltaEntry("hotbkt", 2, 5 * NANO, 7 * NANO, 3 * NANO, 9),
                wire.DeltaEntry("coldbkt", 1, 5 * NANO, NANO, NANO, 5),
            ]
            data, _ = wire.encode_delta_packet(1, 9, (), ents, max_size=ROW)
            self._feed_raw(e1, [data])
            self._feed_python(e2, [data])
            assert e2.flush(timeout=30)
            for nm in ("hotbkt", "coldbkt"):
                r1, r2 = e1.directory.lookup(nm), e2.directory.lookup(nm)
                pn1, el1 = e1.row_view(r1)
                pn2, el2 = e2.row_view(r2)
                assert np.array_equal(pn1, pn2), nm
                assert el1 == el2, nm
            assert e1._hosted_flag[e1.directory.lookup("hotbkt")]
        finally:
            e1.stop()
            e2.stop()

    def test_raw_planes_with_no_valid_packets_release_inline(self):
        eng = self._mk_engine()
        try:
            planes, lengths = planes_of([b"garbage!", b"\x00" * 60])
            released = []
            eng.ingest_raw_planes(
                planes, lengths, release=lambda: released.append(1)
            )
            assert released == [1]
        finally:
            eng.stop()


class TestDeltaPlaneRawPath:
    """on_packet routes through the raw plane when the engine supports
    it — same verdicts, same counters, same folded state as the python
    decode path (PATROL_RAW_INGEST=0)."""

    def _plane_with_engine(self):
        from tests.test_delta import FakeRep, make_plane

        eng = DeviceEngine(
            LimiterConfig(buckets=64, nodes=4), node_slot=0,
            clock=lambda: NANO,
        )
        rep, plane = make_plane()
        rep.repo = TPURepo(eng, send_incast=None)
        return eng, rep, plane

    def test_counters_match_python_path(self, monkeypatch):
        from patrol_tpu.net import delta as delta_mod

        peer = ("127.0.0.1", 1234)
        good = mk_packet(5, 20, name_pool=10, seq=9, acks=(1, 2))
        bad = bytearray(good)
        bad[40] ^= 0xFF
        oob = wire.encode_delta_packet(
            1, 3, (),
            [
                wire.DeltaEntry("x", 99, 0, 5, 5, 0),  # slot out of range
                wire.DeltaEntry("x", 1, 0, 5 * NANO, 0, 0),
            ],
            max_size=ROW,
        )[0]
        traffic = [good, bytes(bad), oob]
        stats = {}
        for raw_mode in (True, False):
            monkeypatch.setattr(delta_mod, "RAW_INGEST", raw_mode)
            eng, rep, plane = self._plane_with_engine()
            try:
                assert (plane.raw_engine() is not None) == raw_mode
                results = [plane.on_packet(bytes(b), peer) for b in traffic]
                assert results == [True, False, True]
                assert eng.flush(timeout=30)
                stats[raw_mode] = {
                    k: v
                    for k, v in plane.stats().items()
                    if k.startswith("wire_delta_rx")
                }
                row = eng.directory.lookup("x")
                assert row is not None
                pn, _ = eng.row_view(row)
                # oob-slot entry skipped, in-range entry folded.
                assert int(pn[1, 0]) == 5 * NANO
                assert int(pn[:, 0].sum()) == 5 * NANO
                stats[(raw_mode, "acked")] = len(
                    plane._peers[peer].pending_acks
                )
            finally:
                eng.stop()
        assert stats[True] == stats[False]
        assert stats[(True, "acked")] == stats[(False, "acked")]

    def test_native_and_numpy_walks_ingest_alike(self, monkeypatch):
        """on_raw_planes with the C++ walk and with the library missing
        (the numpy fallback): the same acks, ``rx_deltas``, rx errors and
        folded state, and the counters name the path each batch took."""
        from patrol_tpu import native as native_mod

        _native_lib()
        peer = ("127.0.0.1", 1234)
        raw = [
            mk_packet(40 + i, 12, name_pool=16, seq=100 + i, acks=(i + 1,))
            for i in range(6)
        ]
        raw += hostile_corpus(seed=11, n=10)
        planes, lengths = _padded(raw, 16)  # 32 rows, as the rx loop pads
        addrs = [peer] * len(raw) + [None] * 16
        names = {
            e.name
            for b in raw
            if (pk := wire.decode_delta_packet(b)) is not None
            for e in pk.entries
        }
        keys = ("rx_walk_native_batches", "rx_walk_numpy_batches", "rx_walk_ns")
        seen = {}
        for path in ("native", "numpy"):
            eng, rep, plane = self._plane_with_engine()
            try:
                before = {k: profiling.COUNTERS.get(k) for k in keys}
                released = []
                with monkeypatch.context() as m:
                    if path == "numpy":
                        m.setattr(native_mod, "load", lambda: None)
                    assert plane.on_raw_planes(
                        planes.copy(), lengths.copy(), addrs,
                        release=lambda: released.append(1),
                    )
                assert eng.flush(timeout=30)
                assert released == [1]
                moved = {k: profiling.COUNTERS.get(k) - before[k] for k in keys}
                assert moved[f"rx_walk_{path}_batches"] == 1
                other = "numpy" if path == "native" else "native"
                assert moved[f"rx_walk_{other}_batches"] == 0
                assert moved["rx_walk_ns"] > 0
                state = {}
                for nm in names:
                    pn, el = eng.row_view(eng.directory.lookup(nm))
                    state[nm] = (pn.tolist(), int(el))
                seen[path] = (
                    list(plane._peers[peer].pending_acks),
                    plane.rx_deltas,
                    plane.rx_errors,
                    state,
                )
            finally:
                eng.stop()
        assert seen["native"] == seen["numpy"]
        acks, rx_deltas, rx_errors, _ = seen["native"]
        assert acks == [
            pk.seq
            for b in raw
            if (pk := wire.decode_delta_packet(b)) is not None and pk.seq
        ]
        assert rx_deltas >= 6 * 12 and rx_errors > 0


@pytest.mark.skipif(
    __import__("patrol_tpu.native", fromlist=["load"]).load() is None,
    reason="native toolchain unavailable",
)
class TestRxRing:
    def test_lease_commit_zero_copy(self):
        from patrol_tpu import native

        ring = native.RxRing(n_planes=2, max_batch=4, row=512)
        try:
            a = ring.lease()
            b = ring.lease()
            assert (a, b) == (0, 1)
            assert ring.lease() is None  # exhausted
            view = ring.plane(a)
            view[0, :4] = [1, 2, 3, 4]
            # Zero-copy: the native pointer sees the write.
            import ctypes

            ptr = ring.lib.pt_rx_ring_plane(ring.h, a)
            raw = (ctypes.c_uint8 * 4).from_address(ptr)
            assert list(raw) == [1, 2, 3, 4]
            ring.commit(a)
            assert ring.lease() == 0  # recycled, lowest-first
            st = ring.stats()
            assert st["rx_ring_lease_reuse"] == 1
            assert st["rx_ring_exhausted"] == 1
        finally:
            ring.commit(0)
            ring.commit(1)
            ring.close()

    def test_native_backend_uses_ring_for_delta_rx(self):
        """2-node native loopback: delta traffic lands through the raw
        ring path (dispatch counter moves) and converges bit-exactly."""
        import socket as pysock
        import time as time_mod

        from patrol_tpu.net.native_replication import NativeReplicator
        from patrol_tpu.net.replication import SlotTable

        def free_port():
            s = pysock.socket(pysock.AF_INET, pysock.SOCK_DGRAM)
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        p1, p2 = free_port(), free_port()
        a1, a2 = f"127.0.0.1:{p1}", f"127.0.0.1:{p2}"
        reps, engines = [], []
        try:
            for me, other, slot in ((a1, a2, 0), (a2, a1, 1)):
                slots = SlotTable(me, [other], max_slots=4)
                rep = NativeReplicator(me, [other], slots, wire_mode="delta")
                eng = DeviceEngine(
                    LimiterConfig(buckets=64, nodes=4), node_slot=slot,
                )
                rep.repo = TPURepo(eng, send_incast=None)
                reps.append(rep)
                engines.append(eng)
            assert reps[0]._rx_ring is not None
            # Handshake, then ship one interval from node 0 to node 1.
            reps[0].delta.mark_capable(("127.0.0.1", p2), 8192)
            before = profiling.COUNTERS.get("ingest_raw_device_dispatches")
            states = [
                wire.from_nanotokens(
                    f"rb{i}", 2 * NANO, NANO, 100 + i, origin_slot=0,
                    cap_nt=NANO, lane_added_nt=NANO, lane_taken_nt=NANO // 2,
                )
                for i in range(50)
            ]
            reps[0].delta.offer(states)
            reps[0].delta.flush()
            # rx_deltas counts an interval once the engine has taken it
            # in; a bound row alone may not hold its lanes yet.
            deadline = time_mod.time() + 10
            while time_mod.time() < deadline:
                if reps[1].delta.rx_deltas >= len(states):
                    break
                time_mod.sleep(0.02)
            assert engines[1].flush(timeout=30)
            row = engines[1].directory.lookup("rb49")
            assert row is not None
            pn, el = engines[1].row_view(row)
            assert int(pn[0, 0]) == NANO and int(pn[0, 1]) == NANO // 2
            assert el == 149
            assert (
                profiling.COUNTERS.get("ingest_raw_device_dispatches") > before
            )
        finally:
            for rep in reps:
                rep.close()
            for eng in engines:
                eng.stop()


class TestAdaptiveCommitBlocks:
    def test_governor_tracks_backlog_and_budget(self):
        eng = DeviceEngine(
            LimiterConfig(buckets=64, nodes=2), node_slot=0,
            clock=lambda: NANO,
        )
        try:
            from patrol_tpu.runtime import engine as engine_mod

            eng._commit_blocks_auto = True
            before = profiling.COUNTERS.get("commit_blocks_auto_resized")
            with eng._cond:
                eng._deltas.clear()
                eng._auto_size_commit_blocks_locked()
                assert eng._commit_blocks == 1  # idle: lowest latency
                # A flood-sized backlog coalesces toward the cap.
                chunk = engine_mod._DeltaChunk(
                    np.zeros(engine_mod.MAX_MERGE_ROWS * 3, np.int64),
                    np.zeros(engine_mod.MAX_MERGE_ROWS * 3, np.int64),
                    np.ones(engine_mod.MAX_MERGE_ROWS * 3, np.int64),
                    np.zeros(engine_mod.MAX_MERGE_ROWS * 3, np.int64),
                    np.zeros(engine_mod.MAX_MERGE_ROWS * 3, np.int64),
                )
                eng._deltas.append(chunk)
                eng._auto_size_commit_blocks_locked()
                assert eng._commit_blocks == 3
                # The measured device-commit cost caps the width: a
                # per-row cost that blows the budget pins blocks at 1.
                eng._commit_row_ns_ewma = float(
                    engine_mod.COMMIT_BUDGET_NS
                )  # 1 row eats the whole budget
                eng._auto_size_commit_blocks_locked()
                assert eng._commit_blocks == 1
                eng._deltas.clear()
            assert (
                profiling.COUNTERS.get("commit_blocks_auto_resized") > before
            )
        finally:
            eng.stop()

    def test_auto_default_and_static_pin(self, monkeypatch):
        from patrol_tpu.runtime import engine as engine_mod

        # The shipped default is auto; a numeric env pins static.
        assert engine_mod._COMMIT_BLOCKS_ENV.strip().lower() == "auto" or (
            engine_mod._COMMIT_BLOCKS_ENV.isdigit()
        )
        from patrol_tpu.runtime.mesh_engine import MeshEngine

        assert MeshEngine._commit_blocks_auto is False
        assert MeshEngine._raw_ingest_capable is False
