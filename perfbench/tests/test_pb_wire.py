"""The benchmark's copy of the v2 delta wire agrees with the program's."""

import random

import numpy as np

from patrol_tpu.net import delta as prog_delta
from patrol_tpu.ops import wire as prog
from perfbench import wire

NANO = wire.NANO


def _entries(rng, n, name_len=None):
    out = []
    for i in range(n):
        ln = name_len or rng.randint(1, 40)
        name = bytes(rng.randrange(1, 256) for _ in range(ln))
        vals = [rng.randrange(0, 1 << 63) for _ in range(4)]
        out.append(wire.Entry(name, rng.randrange(0, 256), *vals))
    return out


def _prog_entries(entries):
    return [prog.DeltaEntry(e.name.decode("utf-8", "surrogateescape"), e.slot, e.cap_nt,
                            e.added_nt, e.taken_nt, e.elapsed_ns) for e in entries]


def test_encode_matches_program_byte_for_byte():
    rng = random.Random(1)
    for trial in range(30):
        ents = _entries(rng, rng.randint(0, 300))
        acks = [rng.randrange(0, 1 << 32) for _ in range(rng.randint(0, 40))]
        seq = rng.randrange(0, 1 << 32)
        size = rng.choice([256, 1500, 8192])
        mine = wire.encode(7, seq, acks, ents, size)
        theirs = prog.encode_delta_packet(7, seq, acks, _prog_entries(ents), size)
        assert mine == theirs


def test_fixed_encoder_matches_program():
    rng = random.Random(2)
    for n in (0, 1, 5, wire.capacity(8192, 12, 32)):
        ents = _entries(rng, n, name_len=12)
        rec = np.zeros(n, wire.entry_dtype(12))
        for i, e in enumerate(ents):
            rec[i] = (12, e.name, e.slot, e.cap_nt, e.added_nt, e.taken_nt, e.elapsed_ns)
        acks = list(range(1, 33))
        mine = wire.encode_fixed(3, 99, acks, rec)
        theirs, packed = prog.encode_delta_packet(3, 99, acks, _prog_entries(ents), 8192)
        assert packed == n and mine == theirs


def test_decode_reads_program_packets_and_acks():
    rng = random.Random(3)
    ents = _entries(rng, 50)
    data, n = prog.encode_delta_packet(4, 12, [5, 6, 7], _prog_entries(ents))
    pkt = wire.decode(data)
    assert pkt.sender_slot == 4 and pkt.seq == 12 and pkt.acks == (5, 6, 7)
    assert list(pkt.entries) == ents[:n]
    bare, _ = prog.encode_delta_packet(4, 0, [9], ())
    assert wire.decode(bare) == wire.Packet(4, 0, (9,), ())
    assert wire.decode(data[:-1] + bytes([(data[-1] + 1) & 0xFF])) is None
    assert prog.decode_delta_packet(wire.encode(1, 2, [3], ents[:3])[0]) is not None


def test_capacity_matches_program():
    for size in (256, 1500, 8192):
        for ln in (1, 12, 200):
            assert wire.capacity(size, ln) == prog.delta_capacity(size, ln)


def test_adverts_match_program():
    for bound in (256, 8192):
        name = prog_delta.DELTA_ADVERT_NAME.encode() + prog_delta._ADVERT_PAYLOAD.pack(bound)
        assert wire.control(wire.ADVERT, bound) == prog_delta._encode_ctrl(name)
        ack = prog_delta.DELTA_ADVERT_ACK_NAME.encode() + prog_delta._ADVERT_PAYLOAD.pack(bound)
        assert wire.parse_control(prog_delta._encode_ctrl(ack)) == (wire.ADVERT_ACK, bound)


def test_probes_match_program():
    from patrol_tpu.net import replication

    for name, mine in ((replication.PROBE_NAME, wire.PROBE), (replication.PROBE_ACK_NAME, wire.PROBE_ACK)):
        theirs = prog.encode(prog.WireState(name=name, added=0.0, taken=0.0, elapsed_ns=0))
        assert wire.control(mine) == theirs
        assert wire.parse_control(theirs) == (mine, 0)


def test_classic_state_packets_read_as_the_program_writes_them():
    own = prog.encode(prog.from_nanotokens(
        "user-0000042", 7 * NANO, 3 * NANO, 123_456_789, origin_slot=0, cap_nt=100 * NANO,
        lane_added_nt=5 * NANO + 17, lane_taken_nt=3 * NANO))
    assert wire.decode_state(own) == (b"user-0000042", 123_456_789, ((0, 5 * NANO + 17, 3 * NANO),))
    snap = [prog.from_nanotokens("user-0000042", 9 * NANO, 4 * NANO, 55, origin_slot=s, cap_nt=100 * NANO,
                                 lane_added_nt=s * NANO, lane_taken_nt=(s + 1) * NANO) for s in range(3)]
    (multi,) = prog.pack_multi(snap)
    lanes = ((0, 0, NANO), (1, NANO, 2 * NANO), (2, 2 * NANO, 3 * NANO))
    assert wire.decode_state(prog.encode(multi)) == (b"user-0000042", 55, lanes)
    assert wire.decode_state(own[:-1] + bytes([(own[-1] + 1) & 0xFF])) is None  # checksum
    assert wire.decode_state(wire.control(wire.PROBE)) is None  # a reserved name
    v1 = prog.encode(prog.WireState(name="user-0000042", added=1.0, taken=0.0, elapsed_ns=5))
    assert wire.decode_state(v1) is None  # no exact lanes


def test_incast_request_matches_program():
    mine = wire.incast_request(b"user-0000042", 2)
    theirs = prog.encode(prog.WireState(name="user-0000042", added=0.0, taken=0.0, elapsed_ns=0,
                                        origin_slot=2, multi_ok=True))
    assert mine == theirs
    st = prog.decode(mine)
    assert st.is_zero() and st.multi_ok and st.origin_slot == 2
