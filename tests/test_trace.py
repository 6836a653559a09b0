"""patrol-scope tests: flight recorder, lattice histograms, the
Prometheus exposition roundtrip, and cross-node take tracing.

The tentpole's three contracts, each pinned here:

* the flight recorder is bounded, dumpable as valid Chrome-trace JSON,
  cheap when disabled (the off-branch micro-test), and auto-snapshots on
  anomalies with damping;
* histograms are a G-Counter-per-bucket lattice — join is commutative /
  associative / idempotent and per-node histograms combine exactly, the
  same merge discipline as the limiter state;
* a sampled take's trace id propagates across the replication wire and
  joins the remote decode/merge spans (2-node cluster, frozen clocks,
  faultnet-clean), while v1-style decoding of trailer-bearing packets is
  unchanged.
"""

import json
import threading
import time

import pytest

from patrol_tpu.utils import histogram as hist_mod
from patrol_tpu.utils import trace as trace_mod
from patrol_tpu.utils.histogram import LatticeHistogram


@pytest.fixture
def recorder():
    """A private FlightRecorder so tests never race the process-global
    one that the engine threads write into."""
    return trace_mod.FlightRecorder(size=128)


class TestFlightRecorder:
    def test_records_and_dumps(self, recorder):
        recorder.record(trace_mod.EV_TICK, 1500, 7)
        recorder.record(trace_mod.EV_FOLD, 250, 3)
        events = recorder.dump()
        assert [e["type"] for e in events] == ["engine.tick", "fold"]
        assert events[0]["dur_ns"] == 1500 and events[0]["arg"] == 7
        assert events[0]["t_ns"] <= events[1]["t_ns"]

    def test_ring_is_bounded_and_keeps_newest(self, recorder):
        for i in range(300):  # size is 128
            recorder.record(trace_mod.EV_TICK, i, i)
        events = recorder.dump()
        assert len(events) == 128
        # Oldest-first, newest retained: the last arg is 299.
        assert events[-1]["arg"] == 299
        assert events[0]["arg"] == 300 - 128

    def test_per_thread_rings(self, recorder):
        def other():
            recorder.record(trace_mod.EV_RX_DECODE, 10, 1)

        t = threading.Thread(target=other, name="rx-test")
        t.start()
        t.join()
        recorder.record(trace_mod.EV_TICK, 20, 1)
        events = recorder.dump()
        assert {e["type"] for e in events} == {"rx.decode", "engine.tick"}
        assert len({e["tid"] for e in events}) == 2

    def test_chrome_trace_is_valid_json(self, recorder):
        recorder.record(trace_mod.EV_H2D_PUT, 3000, 42)
        doc = json.loads(recorder.chrome_trace())
        assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
        ev = doc["traceEvents"][0]
        assert ev["ph"] == "X" and ev["name"] == "h2d.put"
        assert ev["dur"] == pytest.approx(3.0)  # µs
        assert ev["args"]["arg"] == 42

    def test_disabled_branch_records_nothing(self, recorder):
        recorder.enabled = False
        if recorder.enabled:  # the documented hot-path call shape
            recorder.record(trace_mod.EV_TICK, 1, 1)
        assert recorder.dump() == []

    def test_disabled_branch_is_cheap(self, recorder):
        """Pin the off-branch hot-path cost (the bench smoke publishes
        the same number as trace_off_branch_ns). Loose CI-safe bound:
        the branch is one attribute load — even a slow runner stays
        orders of magnitude under 5 µs/op."""
        recorder.enabled = False
        n = 50_000
        t0 = time.perf_counter_ns()
        for _ in range(n):
            if recorder.enabled:
                recorder.record(trace_mod.EV_TICK, 0, 0)
        per_op = (time.perf_counter_ns() - t0) / n
        assert per_op < 5_000, f"disabled branch cost {per_op} ns/op"

    def test_anomaly_snapshots_are_damped_and_bounded(self, recorder):
        recorder.record(trace_mod.EV_TICK, 1, 1)
        assert recorder.snapshot("unit-test") is not None
        # Same reason within the damping window: suppressed.
        assert recorder.snapshot("unit-test") is None
        # A different reason snapshots immediately.
        assert recorder.snapshot("other-reason") is not None
        snaps = recorder.snapshots()
        assert [s["reason"] for s in snaps] == ["unit-test", "other-reason"]
        assert snaps[0]["events"], "snapshot did not freeze the ring"

    def test_take_stall_anomaly_hook(self):
        """A TakeTicket.wait timeout (the caller-visible stall) snapshots
        the process recorder under the take-stall reason."""
        from patrol_tpu.ops.rate import Rate
        from patrol_tpu.runtime.engine import TakeTicket

        tr = trace_mod.TRACE
        # Clear the damping window for this reason.
        with tr._snap_mu:
            tr._last_anomaly.pop("take-stall", None)
        before = len(tr.snapshots())
        t = TakeTicket("b", 0, Rate(), 1, 0)
        assert not t.wait(timeout=0.001)  # never completed
        snaps = tr.snapshots()
        assert len(snaps) >= min(before + 1, 4)
        assert any(s["reason"] == "take-stall" for s in snaps)


class TestLatticeHistogram:
    def test_bucket_placement_and_summary(self):
        h = LatticeHistogram("t")
        for v in (0, 1, 2, 3, 1024, 10**6):
            h.record(v)
        s = h.summary()
        assert s["count"] == 6
        assert s["sum"] == 0 + 1 + 2 + 3 + 1024 + 10**6
        assert s["p50"] <= 1024 <= s["max"]
        # p99 lands in the top occupied bucket's edge (≥ the true max's
        # lower bound, < 2x above it).
        assert 10**6 <= s["p99"] < 2 * 10**6

    def test_negative_clamps_to_zero_bucket(self):
        h = LatticeHistogram("t")
        h.record(-5)
        assert h.count == 1 and h.total == 0 and h.quantile(0.5) == 0

    def test_join_laws(self):
        """The G-Counter-per-bucket lattice: commutative, associative,
        idempotent — the limiter state's own merge discipline."""

        def build(slot, values):
            h = LatticeHistogram("t", nodes=3, node_slot=slot)
            for v in values:
                h.record(v)
            return h

        a_vals, b_vals, c_vals = [1, 50, 900], [7, 7, 2048], [10**5]
        # a ⊔ b == b ⊔ a
        ab = build(0, a_vals)
        ab.join(build(1, b_vals))
        ba = build(1, b_vals)
        ba.join(build(0, a_vals))
        assert ab.to_lattice()["counts"] == ba.to_lattice()["counts"]
        assert ab.to_lattice()["sums"] == ba.to_lattice()["sums"]
        # (a ⊔ b) ⊔ c == a ⊔ (b ⊔ c)
        abc1 = build(0, a_vals)
        abc1.join(build(1, b_vals))
        abc1.join(build(2, c_vals))
        bc = build(1, b_vals)
        bc.join(build(2, c_vals))
        abc2 = build(0, a_vals)
        abc2.join(bc)
        assert abc1.to_lattice()["counts"] == abc2.to_lattice()["counts"]
        # a ⊔ a == a (idempotent)
        aa = build(0, a_vals)
        twin = build(0, a_vals)
        aa.join(twin)
        aa.join(twin)
        assert aa.count == len(a_vals)
        # Merged view sums disjoint node lanes.
        assert ab.count == len(a_vals) + len(b_vals)
        assert ab.total == sum(a_vals) + sum(b_vals)

    def test_lattice_roundtrip_combines_nodes(self):
        """The cross-node story: each node ships its lattice; an
        aggregator joins them and reads cluster-wide quantiles."""
        n0 = LatticeHistogram("take_service_ns", nodes=2, node_slot=0)
        n1 = LatticeHistogram("take_service_ns", nodes=2, node_slot=1)
        for v in (100, 200, 400):
            n0.record(v)
        for v in (10**6, 2 * 10**6):
            n1.record(v)
        agg = LatticeHistogram("take_service_ns", nodes=2)
        agg.join_lattice(n0.to_lattice())
        agg.join_lattice(n1.to_lattice())
        agg.join_lattice(n0.to_lattice())  # duplicate delivery: idempotent
        assert agg.count == 5
        assert agg.total == 700 + 3 * 10**6
        assert agg.quantile(0.99) >= 10**6


class TestExposition:
    def test_render_parse_roundtrip(self):
        reg = hist_mod.HistogramRegistry()
        h = reg.get("probe_ns")
        for v in (1, 1, 5, 1000, 10**7):
            h.record(v)
        text = hist_mod.render_exposition(
            {"engine_ticks": 3, "rate": 1.5, "flag": True, "nested": {}},
            registry=reg,
            uptime_s=2.0,
        )
        parsed = hist_mod.parse_exposition(text)
        assert parsed["types"]["patrol_engine_ticks"] == "gauge"
        assert parsed["samples"][("patrol_engine_ticks", ())] == 3
        # bool/nested stats never leak into the exposition
        assert ("patrol_flag", ()) not in parsed["samples"]
        assert parsed["types"]["patrol_probe_ns"] == "histogram"
        assert parsed["samples"][("patrol_probe_ns_count", ())] == 5
        assert parsed["samples"][("patrol_probe_ns_sum", ())] == 1 + 1 + 5 + 1000 + 10**7
        # cumulative bucket: le="1" holds both 1-valued samples
        assert parsed["samples"][("patrol_probe_ns_bucket", (("le", "1"),))] == 2
        assert parsed["samples"][("patrol_probe_ns_bucket", (("le", "+Inf"),))] == 5
        assert parsed["samples"][("patrol_uptime_seconds", ())] == pytest.approx(2.0)

    def test_parser_rejects_malformed(self):
        with pytest.raises(ValueError):
            hist_mod.parse_exposition("patrol_x{le= 1\n")
        with pytest.raises(ValueError):
            hist_mod.parse_exposition("not a metric line\n")

    def test_parser_rejects_non_cumulative_histogram(self):
        bad = (
            "# TYPE patrol_h histogram\n"
            'patrol_h_bucket{le="1"} 5\n'
            'patrol_h_bucket{le="3"} 2\n'
            'patrol_h_bucket{le="+Inf"} 5\n'
            "patrol_h_sum 9\n"
            "patrol_h_count 5\n"
        )
        with pytest.raises(ValueError, match="non-cumulative"):
            hist_mod.parse_exposition(bad)

    def test_parser_rejects_count_inf_mismatch(self):
        bad = (
            "# TYPE patrol_h histogram\n"
            'patrol_h_bucket{le="+Inf"} 5\n'
            "patrol_h_sum 9\n"
            "patrol_h_count 4\n"
        )
        with pytest.raises(ValueError, match="_count"):
            hist_mod.parse_exposition(bad)

    def test_api_metrics_exposition_parses(self):
        """The /metrics exporter (both fronts route through API._metrics)
        emits parseable exposition including the stage histograms."""
        from patrol_tpu.net.api import API

        api = API(None, stats=lambda: {"engine_ticks": 1})
        parsed = hist_mod.parse_exposition(api._metrics().decode())
        assert parsed["types"]["patrol_take_service_ns"] == "histogram"
        for stage in hist_mod.INGEST_STAGES:
            assert f"patrol_{stage}" in parsed["types"]


class TestSampling:
    def test_sampling_off_returns_none(self):
        trace_mod.set_take_sampling(0)
        assert trace_mod.sample_take() is None

    def test_sampling_rate(self):
        trace_mod.set_take_sampling(4)
        try:
            ids = [trace_mod.sample_take() for _ in range(64)]
            hits = [i for i in ids if i is not None]
            assert len(hits) == 16
            assert len(set(hits)) == 16  # unique ids
            assert all(0 < i < 1 << 63 for i in hits)
        finally:
            trace_mod.set_take_sampling(0)


class TestEngineSpans:
    def test_local_take_and_remote_merge_spans(self):
        """One engine: a sampled take records a take span; an ingested
        delta carrying a trace id records the merge span — the two halves
        the cluster test joins over the wire."""
        from patrol_tpu.models.limiter import LimiterConfig
        from patrol_tpu.ops import wire
        from patrol_tpu.ops.rate import Rate
        from patrol_tpu.runtime.engine import DeviceEngine

        trace_mod.SPANS.clear()
        trace_mod.set_take_sampling(1)
        engine = DeviceEngine(LimiterConfig(buckets=32, nodes=4), node_slot=2)
        try:
            _, ok, _ = engine.take("spanbkt", Rate(freq=5, per_ns=10**9), 1)
            assert ok
            st = wire.from_nanotokens(
                "remote", 2 * 10**9, 10**9, 5, origin_slot=1,
                cap_nt=2 * 10**9, lane_added_nt=10**9, lane_taken_nt=10**9,
                trace_id=424242,
            )
            engine.ingest_delta(st, 1)
            assert engine.flush(10)
        finally:
            trace_mod.set_take_sampling(0)
            engine.stop()
        spans = trace_mod.SPANS.export()
        takes = [s for s in spans if s["kind"] == "take"]
        assert takes and takes[0]["bucket"] == "spanbkt"
        assert takes[0]["node"] == 2 and takes[0]["dur_ns"] >= 0
        merges = trace_mod.SPANS.export(424242)
        assert [s["kind"] for s in merges] == ["merge"]
        assert merges[0]["bucket"] == "remote" and merges[0]["node"] == 2


FROZEN_NS = 1_700_000_000_000_000_000


class TestClusterTraceJoin:
    """Acceptance: a 2-node cluster (frozen clocks, faultnet-clean) shows
    one sampled take's exported trace containing the local take span AND
    the remote decode→merge spans joined by the propagated trace id."""

    def test_cross_node_join(self):
        from tests.test_cluster import Cluster, KeepAliveClient

        trace_mod.SPANS.clear()
        trace_mod.set_take_sampling(1)
        cluster = Cluster(
            2,
            udp_backend="asyncio",
            clock_fn=lambda i: (lambda: FROZEN_NS),
            http_front="python",
        )
        try:
            client = KeepAliveClient(cluster.api_ports[0])
            try:
                for _ in range(3):
                    status, _ = client.take("traced", "5:1h")
                    assert status == 200
            finally:
                client.close()
            deadline = time.monotonic() + 10
            joined = None
            while time.monotonic() < deadline and joined is None:
                spans = trace_mod.SPANS.export()
                by_id = {}
                for s in spans:
                    by_id.setdefault(s["trace_id"], []).append(s)
                for tid, group in by_id.items():
                    kinds = {s["kind"] for s in group}
                    if {"take", "rx_decode", "merge"} <= kinds:
                        joined = group
                        break
                if joined is None:
                    time.sleep(0.05)
            assert joined is not None, (
                f"no fully-joined trace within 10s; spans: "
                f"{trace_mod.SPANS.export()}"
            )
            take = next(s for s in joined if s["kind"] == "take")
            decode = next(s for s in joined if s["kind"] == "rx_decode")
            merge = next(s for s in joined if s["kind"] == "merge")
            # The spans carry bucket name + node id, and the remote spans
            # landed on the OTHER node.
            assert {s["bucket"] for s in joined} == {"traced"}
            assert decode["node"] == merge["node"]
            assert take["node"] != decode["node"]
        finally:
            trace_mod.set_take_sampling(0)
            cluster.close()

    def test_v1_peer_interop_with_trace_trailer(self):
        """A trailer-bearing packet (P2 lane + trace trailer) still
        yields the exact v1 header fields a reference peer reads — the
        trailer bytes are invisible to it (bucket.go reads exactly
        data[25:25+L])."""
        from patrol_tpu.ops import wire
        from patrol_tpu.runtime.bucket import Bucket

        st = wire.from_nanotokens(
            "iv", 3 * 10**9, 10**9, 777, origin_slot=1, cap_nt=3 * 10**9,
            lane_added_nt=10**9, lane_taken_nt=10**9, trace_id=99,
        )
        data = wire.encode(st)
        dec = wire.decode(data)
        assert dec.trace_id == 99
        # The v1 node's merge path (tests/test_interop.py's node) consumes
        # the header scalars only — identical with and without the trace
        # trailer present.
        plain = wire.decode(
            wire.encode(
                wire.from_nanotokens(
                    "iv", 3 * 10**9, 10**9, 777, origin_slot=1,
                    cap_nt=3 * 10**9, lane_added_nt=10**9,
                    lane_taken_nt=10**9,
                )
            )
        )
        assert (dec.added, dec.taken, dec.elapsed_ns, dec.name) == (
            plain.added, plain.taken, plain.elapsed_ns, plain.name,
        )
        b = Bucket(name="iv", added_nt=dec.added_nt, taken_nt=dec.taken_nt,
                   elapsed_ns=dec.elapsed_ns)
        assert b.added_nt == 3 * 10**9


class TestTraceTrailerWire:
    """patrol-scope trace-context trailer (ops/wire.py): appended after
    the P2 trailer, invisible to every decoder that predates it — they
    all read their trailer by self-described size and ignore trailing
    bytes. (Lives here rather than test_wire.py: that module skips
    wholesale when hypothesis is absent.)"""

    @staticmethod
    def _traced(**kw):
        from patrol_tpu.ops.wire import from_nanotokens

        return from_nanotokens(
            "tr", 3 * 10**9, 10**9, 555, origin_slot=2, cap_nt=3 * 10**9,
            **kw,
        )

    def test_roundtrip_on_every_trailer_form(self):
        import dataclasses

        from patrol_tpu.ops import wire

        lane = self._traced(lane_added_nt=7, lane_taken_nt=3, trace_id=0xBEEF)
        d = wire.decode(wire.encode(lane))
        assert d.trace_id == 0xBEEF
        assert (d.origin_slot, d.cap_nt, d.lane_added_nt) == (2, 3 * 10**9, 7)
        cap = self._traced(trace_id=42)
        assert wire.decode(wire.encode(cap)).trace_id == 42
        base = wire.WireState("tr", 1.0, 0.5, 9, origin_slot=1, trace_id=77)
        db = wire.decode(wire.encode(base))
        assert db.trace_id == 77 and db.origin_slot == 1
        multi = dataclasses.replace(
            self._traced(trace_id=101), lanes=((0, 1, 2), (1, 3, 4))
        )
        dm = wire.decode(wire.encode(multi))
        assert dm.lanes == ((0, 1, 2), (1, 3, 4)) and dm.trace_id == 101

    def test_untraced_bytes_are_exact_prefix(self):
        from patrol_tpu.ops import wire

        plain = wire.encode(self._traced(lane_added_nt=7, lane_taken_nt=3))
        traced = wire.encode(
            self._traced(lane_added_nt=7, lane_taken_nt=3, trace_id=5)
        )
        assert traced[: len(plain)] == plain  # pure suffix: old bytes exact
        assert len(traced) == len(plain) + wire.TRACE_TRAILER_SIZE
        assert wire.decode(plain).trace_id is None

    def test_corrupt_checksum_drops_trace_only(self):
        from patrol_tpu.ops import wire

        data = bytearray(
            wire.encode(
                self._traced(lane_added_nt=7, lane_taken_nt=3, trace_id=5)
            )
        )
        data[-1] ^= 0xFF  # mangle the trace checksum
        d = wire.decode(bytes(data))
        assert d.trace_id is None
        assert d.lane_added_nt == 7  # the P2 trailer is untouched

    def test_no_p2_trailer_never_carries_trace(self):
        from patrol_tpu.ops import wire

        st = wire.WireState("v1-name", 1.0, 0.0, 3, trace_id=9)
        d = wire.decode(wire.encode(st))
        assert d.trace_id is None and d.origin_slot is None

    def test_skipped_when_no_room(self):
        from patrol_tpu.ops import wire

        name = "n" * (
            wire.PACKET_SIZE - wire.FIXED_SIZE - wire.TRAILER_LANE_SIZE
        )
        st = wire.from_nanotokens(
            name, 1, 0, 0, origin_slot=0, cap_nt=1,
            lane_added_nt=1, lane_taken_nt=0, trace_id=5,
        )
        data = wire.encode(st)
        assert len(data) <= wire.PACKET_SIZE
        d = wire.decode(data)
        assert d.trace_id is None and d.lane_added_nt == 1

    def test_native_batch_decoder_tolerates_trace_trailer(self):
        """The C++ rx decoder checks tail_len >= trailer size and ignores
        the rest — trailer-bearing packets decode to the same lane values
        on the native path (compat across backends)."""
        import numpy as np

        from patrol_tpu import native
        from patrol_tpu.ops import wire

        if native.load() is None:
            pytest.skip("native toolchain unavailable")
        data = wire.encode(
            self._traced(lane_added_nt=7, lane_taken_nt=3, trace_id=0xFEED)
        )
        pkts = np.zeros((1, wire.PACKET_SIZE), np.uint8)
        pkts[0, : len(data)] = np.frombuffer(data, np.uint8)
        dbuf, n = native.decode_batch_raw(
            pkts, np.array([len(data)], np.int32), None
        )
        assert n == 1 and dbuf.name_lens[0] == 2
        assert dbuf.slots[0] == 2
        assert dbuf.caps[0] == 3 * 10**9
        assert dbuf.lane_a[0] == 7 and dbuf.lane_t[0] == 3


class TestSpans:
    def test_span_records_start_duration_id_and_cause(self, recorder):
        t_before = time.perf_counter_ns()
        with recorder.span(trace_mod.EV_HTTP_PUMP, 3, id=7, cause=5) as sp:
            time.sleep(0.001)
            sp.arg = 4
        tick = recorder.span(trace_mod.EV_TICK).begin()
        tick.cause = 7
        tick.end()
        pump, ev = recorder.dump()
        assert (pump["type"], pump["arg"], pump["id"], pump["cause"]) == (
            "http.pump", 4, 7, 5,
        )
        assert t_before <= pump["t_ns"] and pump["dur_ns"] >= 1_000_000
        assert ev["type"] == "engine.tick" and ev["cause"] == 7
        assert ev["t_ns"] >= pump["t_ns"] + pump["dur_ns"]
        doc = json.loads(recorder.chrome_trace())
        assert doc["traceEvents"][0]["args"]["id"] == 7

    def test_disabled_span_is_one_shared_noop(self, recorder):
        recorder.enabled = False
        a = recorder.span(trace_mod.EV_GC_SWEEP)
        with a as sp:
            sp.arg = 9
        assert a is recorder.span(trace_mod.EV_TICK)
        assert recorder.dump() == []

    def test_event_types_register_once(self):
        k = trace_mod.event_type("test.kernel")
        assert trace_mod.event_type("test.kernel") == k
        assert trace_mod.EVENT_NAMES[k] == "test.kernel"
        assert trace_mod.event_type("engine.tick") == trace_mod.EV_TICK

    def test_record_many_matches_record(self):
        import numpy as np

        vals = [0, 1, 2, 3, 7, 8, 1023, 1024, 10**6, 2**62, 2**63 - 1, -5]
        one, many = LatticeHistogram("a"), LatticeHistogram("a")
        for v in vals:
            one.record(v)
        many.record_many(np.array(vals, np.int64))
        many.record_many(np.array([], np.int64))
        assert one.to_lattice() == many.to_lattice()

    @pytest.mark.parametrize("value, n", [(0, 3), (1023, 13), (2**62, 2), (-5, 4), (7, 0)])
    def test_record_n_matches_record(self, value, n):
        one, shared = LatticeHistogram("a"), LatticeHistogram("a")
        for _ in range(n):
            one.record(value)
        shared.record_n(value, n)
        assert one.to_lattice() == shared.to_lattice()


def _capture_with(tmp_path, work):
    """Run ``work()`` inside a CPU ``jax_trace`` capture (from another
    thread, once the capture runs); → the capture's .xplane.pb path."""
    import threading

    from perfbench import xplane
    from patrol_tpu.utils import profiling

    done = threading.Event()

    def inside():
        deadline = time.monotonic() + 10
        while not trace_mod.TRACE.capturing:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        work()
        done.set()

    t = threading.Thread(target=inside)
    t.start()
    out = profiling.jax_trace(0.5, out_dir=str(tmp_path))
    t.join(10)
    assert done.is_set()
    return xplane.find_xplane(out)


class TestCapture:
    """The flight recorder's spans on the device trace's clock."""

    def test_span_lands_in_capture_only_while_it_runs(self, tmp_path):
        from perfbench import xplane

        tr = trace_mod.TRACE
        before = trace_mod.event_type("test.before")
        inside = trace_mod.event_type("test.inside")
        after = trace_mod.event_type("test.after")
        with tr.span(before):
            pass

        def work():
            with tr.span(inside):
                time.sleep(0.002)

        path = _capture_with(tmp_path, work)
        with tr.span(after):
            pass
        names = {e.name for e in xplane.load_events(path)}
        assert "patrol.test.inside" in names
        assert not names & {"patrol.test.before", "patrol.test.after"}
        assert "patrol.clock" in names

    def test_capture_holds_no_python_tracer_events(self, tmp_path):
        import re

        import jax
        import numpy as np
        from jax.profiler import ProfileData

        f = jax.jit(lambda x: x + 1)

        def work():
            for _ in range(3):
                sum(range(10_000))
                f(np.arange(4)).block_until_ready()

        path = _capture_with(tmp_path, work)
        names = [
            e.name
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines
            for e in line.events
        ]
        assert names
        assert not [n for n in names if re.search(r"\.py:\d+", n)]

    def test_clock_anchor_maps_ring_span_onto_capture(self, tmp_path):
        from jax.profiler import ProfileData

        tr = trace_mod.TRACE
        ev = trace_mod.event_type("test.anchored")

        def work():
            with tr.span(ev, id=4242):
                time.sleep(0.001)

        path = _capture_with(tmp_path, work)
        clocks, spans = [], []
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("patrol.clock"):
                        clocks.append((e.start_ns, dict(e.stats)["mono_ns"]))
                    elif e.name == "patrol.test.anchored":
                        spans.append(e.start_ns)
        assert len(clocks) == 2 and len(spans) == 1
        offset = clocks[0][0] - int(clocks[0][1])
        # The stop anchor reads the same offset.
        assert abs(clocks[1][0] - int(clocks[1][1]) - offset) < 100_000
        ring = [e for e in tr.dump() if e["type"] == "test.anchored" and e["id"] == 4242]
        assert len(ring) == 1
        assert abs(ring[0]["t_ns"] + offset - spans[0]) < 100_000


class TestCounters:
    def test_jit_compiles_counts_a_fresh_compile_not_a_cache_hit(self):
        import jax
        import numpy as np

        from patrol_tpu.runtime import engine  # noqa: F401 - installs the listener
        from patrol_tpu.utils import profiling

        c = profiling.COUNTERS
        f = jax.jit(lambda x: x * 3 + 1)
        x = np.arange(5)
        n0, ns0 = c.get("jit_compiles"), c.get("jit_compile_ns")
        f(x).block_until_ready()
        n1, ns1 = c.get("jit_compiles"), c.get("jit_compile_ns")
        f(x).block_until_ready()
        assert n1 - n0 == 1 and ns1 > ns0
        assert c.get("jit_compiles") == n1

    def test_take_rows_dispatched_against_tickets(self, monkeypatch):
        from patrol_tpu.models.limiter import LimiterConfig
        from patrol_tpu.ops.rate import Rate
        from patrol_tpu.runtime import engine as engine_mod
        from patrol_tpu.utils import profiling

        monkeypatch.setattr(engine_mod, "HOST_FASTPATH", False)
        c = profiling.COUNTERS
        eng = engine_mod.DeviceEngine(LimiterConfig(buckets=64, nodes=4), node_slot=0)
        rate = Rate(freq=100, per_ns=10**9)
        try:
            for names in (
                [f"distinct{i}" for i in range(16)],  # one row per ticket
                ["hot"] * 16,  # one key: folded onto few rows
            ):
                rows0 = c.get("take_rows_dispatched")
                with eng._cond:
                    eng._tick_paused = True
                res = eng.submit_takes_batch(names, [rate] * len(names), [1] * len(names))
                with eng._cond:
                    eng._tick_paused = False
                    eng._cond.notify_all()
                for t, _ in res:
                    assert t.wait(10)
                rows = c.get("take_rows_dispatched") - rows0
                if len(set(names)) == len(names):
                    assert rows == len(names)
                else:
                    assert 1 <= rows < len(names)
        finally:
            eng.stop()

    def test_completion_stamp_rides_the_two_argument_complete(self, monkeypatch):
        """The engine completes tickets through ``complete(remaining, ok)``
        (a wrapper of that form, as the benchmark's fault nodes plant,
        still serves), and the ticket's ``done_ns`` is the stamp its
        take_service_ns sample used."""
        from patrol_tpu.models.limiter import LimiterConfig
        from patrol_tpu.ops.rate import Rate
        from patrol_tpu.runtime import engine as engine_mod
        from patrol_tpu.utils import histogram as hist

        orig = engine_mod.TakeTicket.complete
        monkeypatch.setattr(
            engine_mod.TakeTicket, "complete",
            lambda self, remaining, ok: orig(self, remaining, ok),
        )
        for fastpath in (True, False):
            monkeypatch.setattr(engine_mod, "HOST_FASTPATH", fastpath)
            service = LatticeHistogram("take_service_ns")
            monkeypatch.setattr(hist, "TAKE_SERVICE", service)
            eng = engine_mod.DeviceEngine(LimiterConfig(buckets=16, nodes=2), node_slot=0)
            try:
                ticket, _ = eng.submit_take(f"stamp{fastpath}", Rate(freq=5, per_ns=10**9), 1)
                assert ticket.wait(10) and ticket.ok
                assert service.count == 1
                assert service.total == ticket.done_ns - ticket.t0_ns
            finally:
                eng.stop()
