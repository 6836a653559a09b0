"""The benchmark's own copy of the replication wire it speaks to the node.

Copied from the program's codec (``patrol_tpu/ops/wire.py`` v2 delta
framing and ``patrol_tpu/net/delta.py`` capability adverts) so that a
change to the program cannot change what the benchmark sends or how it
reads the node's answers. ``tests/test_pb_wire.py`` checks that both
agree byte for byte.

v2 delta datagram, all big-endian::

    24 zero bytes | u8 7 | "\\x00pt!dv2"                  (32-byte envelope)
    u8 version=2 | u16 sender_slot | u32 seq | u8 n_acks
    n_acks x u32 acked seq
    u16 count | count x (u8 len | name | u16 slot | u64 cap | u64 added |
                         u64 taken | u64 elapsed)
    u8 checksum (sum of the bytes after the envelope, mod 256)

Control packets are zero-state v1 packets (24 zero bytes, u8 name
length, name) on reserved names: the capability advert
``"\\x00pt!dv2?"`` (answered ``"dv2!"``), each followed by the sender's
u32 receive bound, and the liveness probe ``"\\x00pt!probe"`` (answered
``"probe-ack"``) with no payload.

A node ships a bucket's state the classic way (``patrol_tpu/ops/wire.py``
``encode``) to a peer it has not negotiated deltas with, as after it
heals the peer: a v1 packet (f64 added | f64 taken | u64 elapsed | u8
length | name) and a ``"P2"`` trailer, whose exact form carries the
sender's own lane (u16 slot | u64 cap | u64 added | u64 taken) and whose
multi form a bucket's lanes (u16 slot | u64 cap | u8 K | K x (u16 slot |
u64 added | u64 taken)), each closed by a checksum byte. A peer asks
for a bucket's lanes with an incast request: a zero-state v1 packet for
the bucket's name whose base trailer (u16 slot) sets the multi flag.
"""

from __future__ import annotations

import struct
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

NANO = 1_000_000_000
INT64_MAX = (1 << 63) - 1
FIXED_SIZE = 25
DELTA_NAME = b"\x00pt!dv2"
BASE = FIXED_SIZE + len(DELTA_NAME)  # 32
DELTA_PACKET_SIZE = 8192
MAX_ACKS = 32
_HEAD = struct.Struct(">BHIB")
_ACK = struct.Struct(">I")
_COUNT = struct.Struct(">H")
_ENTRY = struct.Struct(">HQQQQ")
ENTRY_FIXED = _ENTRY.size  # 34
_ENVELOPE = bytes(24) + bytes([len(DELTA_NAME)]) + DELTA_NAME
ADVERT = b"\x00pt!dv2?"
ADVERT_ACK = b"\x00pt!dv2!"
PROBE = b"\x00pt!probe"  # liveness ping; a peer answers PROBE_ACK
PROBE_ACK = b"\x00pt!probe-ack"
_U32 = struct.Struct(">I")
_V1_ELAPSED = struct.Struct(">q")  # at byte 16 of a v1 packet
_TRAILER_LANE = struct.Struct(">2sBHQQQB")  # "P2" | flags | slot | cap | added | taken | check
_MULTI_HEAD = struct.Struct(">2sBHQB")  # "P2" | flags | slot | cap | K
_MULTI_LANE = struct.Struct(">HQQ")  # slot | added | taken
_TRAILER = struct.Struct(">2sBHB")  # "P2" | flags | slot | check
_FLAG_CAP, _FLAG_LANE, _FLAG_MULTI = 0x01, 0x02, 0x04


class Entry(NamedTuple):
    name: bytes
    slot: int
    cap_nt: int
    added_nt: int
    taken_nt: int
    elapsed_ns: int


class Packet(NamedTuple):
    sender_slot: int
    seq: int
    acks: Tuple[int, ...]
    entries: Tuple[Entry, ...]


def entry_size(name_len: int) -> int:
    return 1 + name_len + ENTRY_FIXED


def capacity(max_size: int, name_len: int, n_acks: int = 0) -> int:
    """Entries of one name length that fit one datagram of ``max_size``."""
    room = max_size - BASE - _HEAD.size - n_acks * _ACK.size - _COUNT.size - 1
    return max(0, room // entry_size(name_len))


def _clamp(v: int) -> int:
    return min(max(v, 0), INT64_MAX)


def encode(
    sender_slot: int,
    seq: int,
    acks: Sequence[int],
    entries: Iterable[Entry],
    max_size: int = DELTA_PACKET_SIZE,
) -> Tuple[bytes, int]:
    """→ (datagram, entries packed): ``acks`` (at most 32 kept), then as
    many ``entries`` as fit under ``max_size``. ``seq`` 0 with no entries
    is a bare ack."""
    acks = list(acks)[:MAX_ACKS]
    body = bytearray(_HEAD.pack(2, sender_slot & 0xFFFF, seq & 0xFFFFFFFF, len(acks)))
    for a in acks:
        body += _ACK.pack(a & 0xFFFFFFFF)
    count_off = len(body)
    body += _COUNT.pack(0)
    budget = max_size - BASE - len(body) - 1
    packed = 0
    for e in entries:
        sz = 1 + len(e.name) + ENTRY_FIXED
        if sz > budget or packed >= 0xFFFF:
            break
        body.append(len(e.name))
        body += e.name
        body += _ENTRY.pack(
            e.slot & 0xFFFF, _clamp(e.cap_nt), _clamp(e.added_nt),
            _clamp(e.taken_nt), _clamp(e.elapsed_ns),
        )
        budget -= sz
        packed += 1
    _COUNT.pack_into(body, count_off, packed)
    body.append(sum(body) & 0xFF)
    return _ENVELOPE + bytes(body), packed


def entry_dtype(name_len: int) -> np.dtype:
    """One packed entry of a fixed name length, as the wire lays it out."""
    return np.dtype([
        ("len", "u1"), ("name", f"S{name_len}"), ("slot", ">u2"), ("cap", ">u8"),
        ("added", ">u8"), ("taken", ">u8"), ("elapsed", ">u8"),
    ])


def encode_fixed(sender_slot: int, seq: int, acks: Sequence[int], entries: np.ndarray) -> bytes:
    """:func:`encode` for entries already packed as :func:`entry_dtype`
    records (all names of one length, values in range): the peers' bulk
    path. The caller sizes ``entries`` with :func:`capacity`."""
    acks = list(acks)[:MAX_ACKS]
    head = bytearray(_HEAD.pack(2, sender_slot & 0xFFFF, seq & 0xFFFFFFFF, len(acks)))
    for a in acks:
        head += _ACK.pack(a & 0xFFFFFFFF)
    head += _COUNT.pack(len(entries))
    body = bytes(head) + entries.tobytes()
    check = (sum(head) + int(np.frombuffer(entries.tobytes(), np.uint8).sum(dtype=np.uint64))) & 0xFF
    return _ENVELOPE + body + bytes([check])


def is_delta(data: bytes) -> bool:
    return len(data) > BASE and data[:BASE] == _ENVELOPE


def decode(data: bytes) -> Optional[Packet]:
    """Strict decode of a v2 delta datagram; ``None`` when malformed."""
    end = len(data) - 1
    if end < BASE + _HEAD.size + _COUNT.size or data[:BASE] != _ENVELOPE:
        return None
    if data[end] != sum(data[BASE:end]) & 0xFF:
        return None
    version, sender_slot, seq, n_acks = _HEAD.unpack_from(data, BASE)
    if version != 2 or n_acks > MAX_ACKS:
        return None
    off = BASE + _HEAD.size
    if off + n_acks * _ACK.size + _COUNT.size > end:
        return None
    acks = tuple(_ACK.unpack_from(data, off + 4 * i)[0] for i in range(n_acks))
    off += n_acks * _ACK.size
    (count,) = _COUNT.unpack_from(data, off)
    off += _COUNT.size
    entries: List[Entry] = []
    for _ in range(count):
        if off >= end:
            return None
        n = data[off]
        off += 1
        if off + n + ENTRY_FIXED > end:
            return None
        name = bytes(data[off : off + n])
        off += n
        slot, cap, added, taken, elapsed = _ENTRY.unpack_from(data, off)
        off += ENTRY_FIXED
        if max(cap, added, taken, elapsed) > INT64_MAX:
            return None
        entries.append(Entry(name, slot, cap, added, taken, elapsed))
    if off != end:
        return None
    return Packet(sender_slot, seq, acks, tuple(entries))


def control(name: bytes, rx_bound: Optional[int] = None) -> bytes:
    """A control packet: an advert (``ADVERT``/``ADVERT_ACK``) carrying
    ``rx_bound``, or a probe (``PROBE``/``PROBE_ACK``)."""
    payload = name + (_U32.pack(rx_bound) if rx_bound is not None else b"")
    return bytes(24) + bytes([len(payload)]) + payload


def parse_control(data: bytes) -> Optional[Tuple[bytes, int]]:
    """→ (ADVERT or ADVERT_ACK, the sender's receive bound), (PROBE, 0)
    or (PROBE_ACK, 0); None for anything else."""
    if len(data) <= FIXED_SIZE or data[:24] != bytes(24):
        return None
    name = data[FIXED_SIZE : FIXED_SIZE + data[24]]
    if name in (PROBE, PROBE_ACK):
        return name, 0
    for kind in (ADVERT, ADVERT_ACK):
        if name.startswith(kind) and len(name) >= len(kind) + 4:
            return kind, _U32.unpack_from(name, len(kind))[0]
    return None


def decode_state(data: bytes) -> Optional[Tuple[bytes, int, Tuple[Tuple[int, int, int], ...]]]:
    """A classic state packet's exact lanes: → (name, the bucket's
    elapsed ns, ((slot, added, taken), ...)); ``None`` for a packet that
    is not one, has a reserved name, or carries no valid exact trailer."""
    if len(data) <= FIXED_SIZE:
        return None
    n = data[24]
    name = bytes(data[FIXED_SIZE : FIXED_SIZE + n])
    tail = data[FIXED_SIZE + n :]
    if len(name) != n or not name or name[0] == 0 or tail[:2] != b"P2" or len(tail) < 3:
        return None
    (elapsed,) = _V1_ELAPSED.unpack_from(data, 16)
    flags = tail[2]
    if flags & (_FLAG_CAP | _FLAG_LANE) == _FLAG_CAP | _FLAG_LANE and len(tail) >= _TRAILER_LANE.size:
        _, _, slot, cap, added, taken, check = _TRAILER_LANE.unpack_from(tail)
        if check != sum(tail[: _TRAILER_LANE.size - 1]) & 0xFF or max(cap, added, taken) > INT64_MAX:
            return None
        return name, elapsed, ((slot, added, taken),)
    if flags & _FLAG_MULTI and flags & _FLAG_CAP and not flags & _FLAG_LANE and len(tail) >= _MULTI_HEAD.size + 1:
        _, _, _, cap, k = _MULTI_HEAD.unpack_from(tail)
        size = _MULTI_HEAD.size + k * _MULTI_LANE.size + 1
        if len(tail) < size or tail[size - 1] != sum(tail[: size - 1]) & 0xFF or cap > INT64_MAX:
            return None
        lanes = tuple(_MULTI_LANE.unpack_from(tail, _MULTI_HEAD.size + i * _MULTI_LANE.size) for i in range(k))
        if any(max(a, t) > INT64_MAX for _, a, t in lanes):
            return None
        return name, elapsed, lanes
    return None


def incast_request(name: bytes, slot: int) -> bytes:
    """Ask for a bucket's lanes; the node answers with its multi form."""
    trailer = bytearray(_TRAILER.pack(b"P2", _FLAG_MULTI, slot & 0xFFFF, 0))
    trailer[-1] = sum(trailer[:-1]) & 0xFF
    return bytes(24) + bytes([len(name)]) + name + bytes(trailer)
