"""Device-resident ingest: decode + fold raw wire-v2 delta datagrams on
device (ROADMAP item 1's "make the device the bulk plane" lever).

The r05 wall in one sentence: the host pipeline folds 6.6M deltas/s in
isolation but end-to-end ingest lands at 375k/s, because the wire→state
path ships *folded matrices*, not bytes — every dv2 datagram pays a
Python ``decode_delta_packet`` (per-entry object churn), a host fold,
and a staging copy before the device sees work. This module inverts
that: the rx path ships the **raw datagram byte planes** (uint8[P, 8192]
rows straight out of the recvmmsg ring) and ONE dispatch performs the
framing walk, entry extraction, checksum/validation verdicts,
sentinel-padding of invalid packets, and the scatter-max fold into
state (:func:`decode_fold_raw`).

Division of labor with the host (the part a device kernel cannot do):

* **row resolution** — bucket names live in the host directory's hash
  table, so the host runs a *structure walk* (:func:`walk`: one C++ call
  per batch, or :func:`host_walk`, the numpy reference, where the native
  library is missing) that extracts per-entry name
  offsets/hashes and the header/ack fields, resolves rows through the
  existing directory pass, and hands the kernel a ``rows[P, E]`` plan
  (``FOLD_PAD_ROW`` marks entries the fold must skip: directory-miss
  drops, control-channel names, out-of-range slots);
* **host-lane split** — rows currently host-resident are flagged in the
  ``hosted[P, E]`` input; the kernel masks them OUT of the fold and
  returns a ``hosted_mask`` output (valid ∩ hosted) plus the decoded
  entry values, which the engine absorbs through the existing
  host-lane join (engine.ingest_raw_planes).

Validation is **bit-identical to ops/wire.py::decode_delta_packet** —
all-or-nothing per packet: envelope (24 zero bytes, reserved name),
checksum, version, ack-vector bounds, per-entry framing bounds, bit-63
value guards, exact end-of-payload. The differential sweep in
tests/test_ingest.py pins verdicts AND folded state against the Python
decoder over the hostile corpus (truncations, flips, trailing garbage,
mixed valid/invalid planes).

Kernel forms:

* :func:`decode_fold_raw` — the XLA implementation (wide vectorized
  framing checks over [P, E] lanes + one scatter-max), the production
  path on every backend.
* :func:`host_walk` (numpy) and :func:`walk` (the native walk when the
  library loads) — the host reference the kernel's verdicts are pinned
  against; the host join is the reference for its folded state.

Algebra: the fold leg is the identical lattice join as
``ops/delta.delta_fold`` (elementwise int64 max, ``mode="drop"``
sentinels), so the full PTP001-005 obligation set holds; registered in
``ops/obligations.py::PROVE_ROOTS`` under the ``raw_ingest`` model
(analysis/prove.py): packet-order commutativity, duplicated-plane
idempotence, join monotonicity, and strict corruption rejection are
machine-checked through the REAL kernel, and the seeded
accept-bad-checksum / add-instead-of-max mutations are demonstrably
rejected (tests/test_prove.py).
"""

from __future__ import annotations

import time
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from patrol_tpu import native
from patrol_tpu.models.limiter import LimiterState
from patrol_tpu.ops import wire
from patrol_tpu.ops.merge import FOLD_PAD_ROW
from patrol_tpu.utils import profiling

# Framing constants, mirrored from ops/wire.py (the codec is the spec;
# these are the offsets its struct layout implies).
RAW_PLANE_BYTES = wire.DELTA_PACKET_SIZE  # 8192: the rx ring row width
# The rx ring's recvmmsg batch bound: a raw dispatch pads its plane count
# to a power of two up to this, so 1..RAW_MAX_PLANES are the shapes the
# engine's warm-up compiles.
RAW_MAX_PLANES = 512
_BASE = 32  # envelope: 25-byte v1 header + 7-byte reserved name
_HEAD = 8  # version u8 | sender_slot u16 | seq u32 | n_acks u8
_ACK = 4
_COUNT = 2
_ENTRY_TAIL = 34  # slot u16 | cap u64 | added u64 | taken u64 | elapsed u64
_MIN_LEN = _BASE + _HEAD + _COUNT + 1  # 43: header + count + checksum
_NAME = np.frombuffer(wire.DELTA_CHANNEL_NAME.encode(), np.uint8)
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def max_entries(row_bytes: int) -> int:
    """Entry-ordinal bound for one plane row: the most entries a legal
    packet of ``row_bytes`` can carry (minimum entry = empty name)."""
    return max(1, (row_bytes - _MIN_LEN) // (1 + _ENTRY_TAIL))


MAX_RAW_ENTRIES = max_entries(RAW_PLANE_BYTES)  # 232 at the 8-KiB row


def dv2_mask(planes: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Vectorized envelope test over a recv batch: which rows are dv2
    delta datagrams (the numpy twin of wire.is_delta_packet) — routes
    the raw batch path before the generic per-packet dispatch."""
    n = len(sizes)
    if n == 0:
        return np.zeros(0, dtype=bool)
    head = planes[:n, :_BASE]
    return (
        (np.asarray(sizes[:n]) > _BASE)
        & (head[:, :24] == 0).all(axis=1)
        & (head[:, 24] == len(_NAME))
        & (head[:, 25:_BASE] == _NAME).all(axis=1)
    )


class RawWalk(NamedTuple):
    """The host structure walk's view of one plane batch: packet
    verdicts + header/ack fields (the delta plane's bookkeeping) and the
    per-entry name structure the directory pass consumes. Shapes:
    scalars ``[P]``, entry fields ``[P, E]``; entries past a packet's
    count (or of an invalid packet) are zero-filled."""

    ok: np.ndarray  # bool[P] — the all-or-nothing packet verdict
    sender_slot: np.ndarray  # int32[P]
    seq: np.ndarray  # int64[P] (u32 on the wire)
    n_acks: np.ndarray  # int32[P]
    acks: np.ndarray  # int64[P, 32]
    count: np.ndarray  # int32[P] live entries (0 when not ok)
    name_off: np.ndarray  # int32[P, E] offset of the name bytes
    name_len: np.ndarray  # int32[P, E]
    name_hash: np.ndarray  # uint64[P, E] FNV-1a (directory routing)
    slot: np.ndarray  # int64[P, E]
    cap: np.ndarray  # int64[P, E]
    added: np.ndarray  # int64[P, E]
    taken: np.ndarray  # int64[P, E]
    elapsed: np.ndarray  # int64[P, E]


def _np_be(planes: np.ndarray, pi: np.ndarray, off: np.ndarray, nbytes: int):
    """Big-endian uint read at per-row offsets → uint64[P] (vectorized
    gather; callers guarantee off+nbytes stays inside the plane row)."""
    acc = np.zeros(len(pi), np.uint64)
    for k in range(nbytes):
        acc = (acc << np.uint64(8)) | planes[pi, off + k].astype(np.uint64)
    return acc


def host_walk(planes: np.ndarray, lengths: np.ndarray) -> "RawWalk":
    """The vectorized host structure walk: verdicts bit-identical to
    ``wire.decode_delta_packet`` plus the name structure (offset, length,
    FNV hash) the directory pass needs and the numeric fields the
    host-lane absorb and cap-adoption tails use. One python-level loop
    iteration per entry ORDINAL (≤ :data:`MAX_RAW_ENTRIES`), each
    vectorized across every packet still walking — not per entry. The
    reference the C++ walk (:func:`walk`) is held to, and its fallback."""
    planes = np.asarray(planes)
    P, row = planes.shape
    E = max_entries(row)
    lengths = np.asarray(lengths, np.int64)
    pidx = np.arange(P)
    end = lengths - 1  # checksum byte offset
    safe_end = np.clip(end, 0, row - 1)

    ok = (lengths >= _MIN_LEN) & (lengths <= row)
    ok &= (planes[:, :24] == 0).all(axis=1)
    ok &= planes[:, 24] == len(_NAME)
    ok &= (planes[:, 25:_BASE] == _NAME).all(axis=1)
    # Checksum: sum(data[32:end]) & 0xFF == data[end]. Bytes past the
    # datagram length are stale ring contents and MUST NOT contribute.
    col = np.arange(row)
    body = np.where(
        (col[None, :] >= _BASE) & (col[None, :] < end[:, None]), planes, 0
    )
    ok &= (body.sum(axis=1) & 0xFF) == planes[pidx, safe_end]
    ok &= planes[:, _BASE] == wire.DELTA_VERSION
    sender_slot = (
        planes[:, _BASE + 1].astype(np.int32) << 8
    ) | planes[:, _BASE + 2]
    seq = _np_be(planes, pidx, np.full(P, _BASE + 3), 4).astype(np.int64)
    n_acks = planes[:, _BASE + 7].astype(np.int32)
    ok &= n_acks <= wire.DELTA_MAX_ACKS
    off0 = _BASE + _HEAD + _ACK * n_acks.astype(np.int64)
    ok &= off0 + _COUNT <= end
    # The STRUCTURE walk below is gated only on walkability (safe cursor
    # bounds), NOT on the envelope/checksum/version verdicts: the offsets
    # are a framing PROPOSAL for the device kernel, which re-validates
    # everything itself and must stay the verdict authority — a host
    # walk that withheld offsets from checksum-failed packets would mask
    # an in-kernel validation bug from the prover's mutation sweep.
    walkable = (
        (lengths >= _MIN_LEN)
        & (lengths <= row)
        & (n_acks <= wire.DELTA_MAX_ACKS)
        & (off0 + _COUNT <= end)
    )
    acks = np.zeros((P, wire.DELTA_MAX_ACKS), np.int64)
    for k in range(wire.DELTA_MAX_ACKS):
        sel = ok & (n_acks > k)
        if sel.any():
            si = np.flatnonzero(sel)
            acks[si, k] = _np_be(
                planes, si, (_BASE + _HEAD + _ACK * k) * np.ones(len(si), np.int64), 4
            ).astype(np.int64)
    count_off = np.clip(off0, 0, row - 2)
    count = (
        (planes[pidx, count_off].astype(np.int64) << 8)
        | planes[pidx, count_off + 1]
    ).astype(np.int64)
    count = np.where(walkable, count, 0)

    name_off = np.zeros((P, E), np.int32)
    name_len = np.zeros((P, E), np.int32)
    entry_seen = np.zeros((P, E), bool)

    # Structure walk: ONLY the cursor advance and framing bounds run
    # per-ordinal; field extraction happens once, flat, below.
    off = np.where(walkable, off0 + _COUNT, 0).astype(np.int64)
    walking = walkable.copy()
    for i in range(E):
        active = walking & (count > i)
        if not active.any():
            break
        ai = np.flatnonzero(active)
        o = off[ai]
        # Python: ``if off >= end: return None`` then name_len = data[off];
        # off += 1; ``if off + nl + 34 > end: return None``.
        in_bounds = o < end[ai]
        nl = planes[ai, np.clip(o, 0, row - 1)].astype(np.int64)
        fits = in_bounds & (o + 1 + nl + _ENTRY_TAIL <= end[ai])
        bad = ai[~fits]
        walking[bad] = False
        ok[bad] = False
        gi = ai[fits]
        if gi.size:
            nlg = nl[fits]
            name_off[gi, i] = off[gi] + 1
            name_len[gi, i] = nlg
            entry_seen[gi, i] = True
            off[gi] = off[gi] + 1 + nlg + _ENTRY_TAIL
    # A count the walk could not finish (count > E physically cannot fit)
    # and a payload that does not end exactly at the checksum both reject.
    ok &= count <= E
    ok &= off == end

    # Flat field extraction over every structurally-walked entry. The
    # bit-63 guard applies here: any value ≥ 2^63 rejects the WHOLE
    # packet (decode_delta_packet's max(...) > _INT64_MAX check) — field
    # values never change the cursor walk, so deferring the check out of
    # the loop is exact.
    slot = np.zeros((P, E), np.int64)
    cap = np.zeros((P, E), np.int64)
    added = np.zeros((P, E), np.int64)
    taken = np.zeros((P, E), np.int64)
    elapsed = np.zeros((P, E), np.int64)
    spi, sei = np.nonzero(entry_seen)
    if spi.size:
        # One [n, 34] tail gather instead of 34 per-byte gathers (the
        # walked entries guarantee tail+34 ≤ end, so no clipping).
        tails = (name_off[spi, sei] + name_len[spi, sei]).astype(np.int64)
        b34 = planes[spi[:, None], tails[:, None] + np.arange(_ENTRY_TAIL)]
        b34 = b34.astype(np.uint64)

        def _be64(o: int) -> np.ndarray:
            acc = b34[:, o]
            for k in range(1, 8):
                acc = (acc << np.uint64(8)) | b34[:, o + k]
            return acc

        slot[spi, sei] = ((b34[:, 0] << np.uint64(8)) | b34[:, 1]).astype(
            np.int64
        )
        c = _be64(2)
        a = _be64(10)
        t = _be64(18)
        e = _be64(26)
        hi = np.uint64(1) << np.uint64(63)
        bit63 = ((c | a | t | e) & hi) != 0
        if bit63.any():
            ok[spi[bit63]] = False
        cap[spi, sei] = c.astype(np.int64)
        added[spi, sei] = a.astype(np.int64)
        taken[spi, sei] = t.astype(np.int64)
        elapsed[spi, sei] = e.astype(np.int64)
    count = np.where(ok, count, 0).astype(np.int32)

    # Zero the VALUE fields of rejected packets: a RawWalk never leaks
    # values from a packet its verdict refused (the engine masks on ok
    # anyway). The STRUCTURE fields (name_off/name_len) stay — they are
    # the kernel's framing proposal, and the kernel must judge even
    # packets the host verdict refused (see the walkable note above).
    dead = ~ok
    if dead.any():
        for arr in (slot, cap, added, taken, elapsed):
            arr[dead] = 0

    # FNV-1a over the live entry names, flattened: one vectorized loop
    # over byte POSITIONS (bounded by the longest live name, ≤255).
    name_hash = np.zeros((P, E), np.uint64)
    live = ok[:, None] & (np.arange(E)[None, :] < count[:, None])
    pi, ei = np.nonzero(live)
    if pi.size:
        offs = name_off[pi, ei].astype(np.int64)
        lens = name_len[pi, ei].astype(np.int64)
        h = np.full(pi.size, _FNV_OFFSET)
        maxlen = int(lens.max()) if lens.size else 0
        with np.errstate(over="ignore"):
            for k in range(maxlen):
                m = lens > k
                if not m.any():
                    break
                b = planes[pi[m], offs[m] + k].astype(np.uint64)
                h[m] = (h[m] ^ b) * _FNV_PRIME
        name_hash[pi, ei] = h

    return RawWalk(
        ok=ok,
        sender_slot=sender_slot.astype(np.int32),
        seq=seq,
        n_acks=np.where(ok, n_acks, 0).astype(np.int32),
        acks=acks,
        count=count,
        name_off=name_off,
        name_len=name_len,
        name_hash=name_hash,
        slot=slot,
        cap=cap,
        added=added,
        taken=taken,
        elapsed=elapsed,
    )


def _native_walk(lib, planes: np.ndarray, lengths: np.ndarray):
    """:func:`host_walk` in one C++ call (``pt_raw_walk``): the same
    :class:`RawWalk`, bit for bit, with the interpreter lock released once
    for the whole batch instead of at every numpy call of the per-ordinal
    walk. Outputs are fresh arrays the call fills whole, since the engine
    keeps the walk's arrays past this batch. None when the call refuses
    the geometry (a row too short for the header)."""
    planes = np.ascontiguousarray(planes, np.uint8)
    P, row = planes.shape
    E = max_entries(row)
    lengths = np.ascontiguousarray(lengths, np.int64)
    if lengths.shape != (P,):
        raise ValueError(f"{lengths.shape} lengths for {P} plane rows")
    w = RawWalk(
        ok=np.empty(P, bool),
        sender_slot=np.empty(P, np.int32),
        seq=np.empty(P, np.int64),
        n_acks=np.empty(P, np.int32),
        acks=np.empty((P, wire.DELTA_MAX_ACKS), np.int64),
        count=np.empty(P, np.int32),
        name_off=np.empty((P, E), np.int32),
        name_len=np.empty((P, E), np.int32),
        name_hash=np.empty((P, E), np.uint64),
        slot=np.empty((P, E), np.int64),
        cap=np.empty((P, E), np.int64),
        added=np.empty((P, E), np.int64),
        taken=np.empty((P, E), np.int64),
        elapsed=np.empty((P, E), np.int64),
    )
    rc = lib.pt_raw_walk(
        planes, lengths, P, row, E, w.ok.view(np.uint8), *w[1:]
    )
    return w if rc == 0 else None


def walk(planes: np.ndarray, lengths: np.ndarray) -> RawWalk:
    """The rx paths' structure walk: the native walk when the library
    loads, :func:`host_walk` (the numpy reference) otherwise. Counts the
    path taken (``rx_walk_native_batches`` / ``rx_walk_numpy_batches``)
    and the walk's wall time (``rx_walk_ns``)."""
    t0 = time.perf_counter_ns()
    lib = native.load()
    out = None if lib is None else _native_walk(lib, planes, lengths)
    if out is None:
        out = host_walk(planes, lengths)
        profiling.COUNTERS.inc("rx_walk_numpy_batches")
    else:
        profiling.COUNTERS.inc("rx_walk_native_batches")
    profiling.COUNTERS.inc("rx_walk_ns", time.perf_counter_ns() - t0)
    return out


def gather_name_rows(
    planes: np.ndarray,
    pkt_idx: np.ndarray,
    name_off: np.ndarray,
    name_len: np.ndarray,
) -> np.ndarray:
    """Zero-padded uint8[n, 256] name rows for flat entries addressed by
    (packet index, byte offset) — the layout the directory's vectorized
    hash-table lookup verifies, built with one 2-D gather."""
    n = len(pkt_idx)
    out = np.zeros((n, 256), np.uint8)
    if n == 0:
        return out
    row = planes.shape[1]
    lens = np.minimum(name_len.astype(np.int64), 255)
    w = int(lens.max())
    if w == 0:
        return out
    # Gather only the longest live name's width (typical names are a few
    # bytes — a fixed 256-wide gather was the raw path's top host cost).
    cols = np.arange(w)[None, :]
    idx = np.clip(name_off.astype(np.int64)[:, None] + cols, 0, row - 1)
    vals = planes[pkt_idx.astype(np.int64)[:, None], idx]
    out[:, :w] = np.where(cols < lens[:, None], vals, 0)
    return out


# ---------------------------------------------------------------------------
# Device decode core. Pure jnp on values; the framing walk is wide
# vectorized ops over [P, E] entry lanes.


def _device_decode(planes: jax.Array, lengths: jax.Array, entry_off: jax.Array):
    """→ (ok[P], count[P], slot, cap, added, taken, elapsed — all
    int64[P, E]). The in-dispatch framing walk + checksum/validation
    verdicts, bit-identical to wire.decode_delta_packet.

    ``entry_off`` is the host walk's per-entry offset PROPOSAL (the
    length-byte position of each entry; the host computed it anyway for
    the directory pass). The kernel never trusts it: it re-derives each
    entry's name length from the plane bytes and verifies the WHOLE
    framing chain — first offset at header+count, each successor exactly
    ``off + 1 + name_len + 34``, every entry inside the payload, the
    last one ending exactly at the checksum byte — plus envelope,
    checksum, version, ack bounds and the bit-63 value guards. Because
    the chain is fully determined by the bytes, a packet passes iff the
    proposal IS the true chain and that chain satisfies every check the
    python decoder applies: a lying host plan can only reject, never
    smuggle. This trades the r15-draft ``lax.scan`` framing walk (one
    sequential step per entry ordinal — measured ~50 ms/dispatch of pure
    small-op overhead on XLA:CPU) for ~30 wide vectorized ops over
    [P, E] lanes."""
    P, row = planes.shape
    E = entry_off.shape[1]
    pl32 = planes.astype(jnp.int32)
    pidx = jnp.arange(P)
    lengths = lengths.astype(jnp.int64)
    end = lengths - 1
    safe_end = jnp.clip(end, 0, row - 1)

    ok = (lengths >= _MIN_LEN) & (lengths <= row)
    ok &= (pl32[:, :24] == 0).all(axis=1)
    ok &= pl32[:, 24] == len(_NAME)
    for k, b in enumerate(_NAME.tolist()):
        ok &= pl32[:, 25 + k] == b
    col = jnp.arange(row)
    body = jnp.where(
        (col[None, :] >= _BASE) & (col[None, :] < end[:, None]), pl32, 0
    )
    ok &= (body.sum(axis=1) & 0xFF) == pl32[pidx, safe_end]
    ok &= pl32[:, _BASE] == wire.DELTA_VERSION
    n_acks = pl32[:, _BASE + 7].astype(jnp.int64)
    ok &= n_acks <= wire.DELTA_MAX_ACKS
    off0 = _BASE + _HEAD + _ACK * n_acks
    ok &= off0 + _COUNT <= end
    count_off = jnp.clip(off0, 0, row - 2)
    count = (
        pl32[pidx, count_off].astype(jnp.int64) << 8
    ) | pl32[pidx, count_off + 1].astype(jnp.int64)
    count = jnp.where(ok, count, 0)
    ok &= count <= E

    # Framing-chain re-validation of the proposal, vectorized.
    eo = entry_off.astype(jnp.int64)
    cols = jnp.arange(E)[None, :]
    cmask = cols < jnp.minimum(count, E)[:, None]
    nl = pl32[pidx[:, None], jnp.clip(eo, 0, row - 1)].astype(jnp.int64)
    tail = eo + 1 + nl
    nxt = tail + _ENTRY_TAIL
    in_bounds = (eo < end[:, None]) & (nxt <= end[:, None])
    ok &= jnp.where(cmask, in_bounds, True).all(axis=1)
    first_ok = jnp.where(count > 0, eo[:, 0] == off0 + _COUNT, True)
    succ_ok = jnp.where(
        cmask[:, 1:], eo[:, 1:] == nxt[:, :-1], True
    ).all(axis=1)
    last_idx = jnp.clip(count - 1, 0, E - 1)
    last_end = jnp.take_along_axis(nxt, last_idx[:, None], axis=1)[:, 0]
    end_ok = jnp.where(count > 0, last_end == end, off0 + _COUNT == end)
    ok &= first_ok & succ_ok & end_ok

    # Entry extraction: one [P, E, 34] byte gather, big-endian folds.
    idx34 = jnp.clip(tail[:, :, None] + jnp.arange(_ENTRY_TAIL), 0, row - 1)
    b34 = pl32[pidx[:, None, None], idx34].astype(jnp.int64)
    slot = (b34[..., 0] << 8) | b34[..., 1]

    def be64(o: int) -> jax.Array:
        acc = b34[..., o]
        for k in range(1, 8):
            acc = (acc << 8) | b34[..., o + k]
        return acc

    cap = be64(2)
    added = be64(10)
    taken = be64(18)
    elapsed = be64(26)
    # Negative int64 == u64 bit 63 set: reject the whole packet (the
    # python decoder's max(...) > _INT64_MAX check).
    bit63 = (cap < 0) | (added < 0) | (taken < 0) | (elapsed < 0)
    ok &= ~jnp.where(cmask, bit63, False).any(axis=1)
    count = jnp.where(ok, count, 0)
    return ok, count, slot, cap, added, taken, elapsed


def _decode_fold_core(
    state: LimiterState,
    planes: jax.Array,
    lengths: jax.Array,
    entry_off: jax.Array,
    rows: jax.Array,
    hosted: jax.Array,
):
    """Decode + fold, pure: → (state', ok[P], entry_ok[P,E],
    hosted_mask[P,E], slot, cap, added, taken, elapsed). ``entry_off``
    is the host walk's framing proposal the kernel re-validates (see
    _device_decode); ``rows`` is the host directory plan (FOLD_PAD_ROW
    sentinels mark entries the fold must skip); ``hosted`` flags
    host-resident rows, masked OUT of the fold and surfaced in
    ``hosted_mask`` for the engine's host-lane absorb tail."""
    E = rows.shape[1]
    ok, count, slot, cap, added, taken, elapsed = _device_decode(
        planes, lengths, entry_off
    )
    live = ok[:, None] & (jnp.arange(E)[None, :] < count[:, None])
    nodes = state.pn.shape[1]
    entry_ok = live & (slot >= 0) & (slot < nodes)
    hosted_mask = entry_ok & hosted
    fold = entry_ok & ~hosted
    frows = jnp.where(fold, rows, FOLD_PAD_ROW)
    fslots = jnp.where(fold, slot, 0).astype(jnp.int32)
    a = jnp.where(fold, added, 0)
    t = jnp.where(fold, taken, 0)
    e = jnp.where(fold, jnp.maximum(elapsed, 0), 0)
    pair = jnp.stack([a, t], axis=-1)
    pn = state.pn.at[frows, fslots].max(pair, mode="drop")
    el = state.elapsed.at[frows].max(e, mode="drop")
    return (
        LimiterState(pn=pn, elapsed=el),
        ok,
        entry_ok,
        hosted_mask,
        slot,
        cap,
        added,
        taken,
        elapsed,
    )


def decode_fold_raw(
    state: LimiterState,
    planes: jax.Array,
    lengths: jax.Array,
    entry_off: jax.Array,
    rows: jax.Array,
    hosted: jax.Array,
):
    """The registered kernel root (PROVE_ROOTS ``ops.ingest.
    decode_fold_raw``): raw dv2 byte planes → joined state + verdicts in
    one dispatch. See module docs for the contract."""
    return _decode_fold_core(state, planes, lengths, entry_off, rows, hosted)


decode_fold_raw_jit = partial(jax.jit, donate_argnums=0)(decode_fold_raw)
