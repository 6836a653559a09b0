"""The node's replication peers, played by the benchmark.

Each peer is a UDP socket that the node is configured with
(``--peer-addr``). It speaks the v2 delta plane as a peer node does:
it answers the node's capability advert and its liveness probes,
ships its own PN lane as
delta intervals (absolute values, so a retransmit is an idempotent
join), keeps at most ``window`` intervals unacknowledged, resends an
interval that has waited ``retx_ms`` for its ack, and acknowledges
every interval the node ships to it. All peers of a run share one
thread and one ``poll`` loop.

The resend timer adapts as a TCP sender's does (RFC 6298): it starts at
``retx_ms``, follows the smoothed ack latency plus four times its
variation (never below ``retx_ms``), takes no sample from a resent
interval (Karn), and doubles for each resend of the same interval, up to
``MAX_RTO_NS``. The program's own delta plane resends on a fixed 160 ms
timer; against acks that wait seconds for the ingest, a fixed timer
floods the node with duplicate intervals and its throughput swings by a
factor of ten from run to run (PERF.md), so the peers here do not.

What the peers keep is what the reference needs: each peer's lane as
shipped, with the time each value was first sent (every acknowledged
interval promised the node holds it), and every value of the node's own
lane the node published, in a delta interval or a classic state packet
(which it sends until deltas are negotiated again after a heal), with
the time it arrived.
"""

from __future__ import annotations

import select
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from perfbench import wire

NANO = wire.NANO
MAX_RTO_NS = 8_000_000_000
# The peers live on their own loopback address: the node's
# "127.0.0.1:port" then sorts before every peer's, so the node always
# takes lane 0 of the sorted member list. Its lane is a static argument
# of its take kernel, and a lane that moved with the ports would miss the
# compile cache on every boot.
PEER_HOST = "127.0.0.2"


def member_slots(node_addr: str, peer_addrs: List[str]) -> Dict[str, int]:
    """The lane of each member: its rank in the sorted member list, the
    rule a statically configured cluster uses."""
    return {a: i for i, a in enumerate(sorted(set(peer_addrs) | {node_addr}))}


class Peer:
    """One peer's lane state, interval log and statistics."""

    def __init__(self, sock: socket.socket, slot: int, n_keys: int, name_len: int):
        self.sock = sock
        self.addr = "%s:%d" % sock.getsockname()
        self.slot = slot
        self.name_len = name_len
        # This peer's lane, per key (nanotokens / ns), as last shipped.
        self.added = np.zeros(n_keys, np.int64)
        self.taken = np.zeros(n_keys, np.int64)
        self.elapsed = np.zeros(n_keys, np.int64)
        # (first send ns, keys, taken) of every interval: the spend the
        # node may have held at any moment.
        self.shipped: List[Tuple[int, np.ndarray, np.ndarray]] = []
        self.dirty: Dict[int, None] = {}  # keys to ship, in first-dirty order
        self.next_seq = 1
        # seq -> [datagram, first send ns, last send ns, entries, timeout ns,
        #         resent]
        self.unacked: Dict[int, list] = {}
        self.srtt_ns = 0.0  # 0: no sample yet
        self.rttvar_ns = 0.0
        self.intervals: List[Tuple[int, int, int]] = []  # (seq, first send ns, entries)
        self.acked_at: Dict[int, int] = {}  # seq -> first ack ns
        self.retransmits = 0
        self.pending_acks: List[int] = []  # node interval seqs to acknowledge
        self.node_rx_bound = 0  # 0 until the node answers our advert
        self.rx_errors = 0

    def ship_capacity(self) -> int:
        return wire.capacity(self.node_rx_bound, self.name_len, wire.MAX_ACKS)


class PeerSet:
    """The peers of one run and the thread that drives them.

    ``on_tick(peer_set, now_ns, peers)`` is the traffic's hook, called
    for every peer on each tick (``flush_ms``) and, where the hook has
    ``on_ack`` set, for one peer after an ack frees room in its window;
    it marks keys dirty on the peers. The set ships whatever is dirty while
    windows have room, then retransmits and acknowledges.
    """

    def __init__(
        self,
        n_peers: int,
        names: List[bytes],
        cap_nt: int,
        window: int,
        retx_ms: float,
        flush_ms: float,
    ):
        self.names = np.array(names, dtype=f"S{len(names[0])}")
        if any(len(n) != len(names[0]) for n in names):
            raise ValueError("key names must share one length")
        self.name_len = len(names[0])
        self.cap_nt = cap_nt
        self.window = window
        self.retx_ns = int(retx_ms * 1e6)
        self.flush_ns = int(flush_ms * 1e6)
        self.peers: List[Peer] = []
        for _ in range(n_peers):
            s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8 << 20)
            s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8 << 20)
            s.bind((PEER_HOST, 0))
            s.setblocking(False)
            self.peers.append(Peer(s, -1, len(names), self.name_len))
        self.node: Optional[Tuple[str, int]] = None
        self.node_slot = -1
        # The node's own lane as it published it (max over its intervals),
        # and every published value: (key, arrival ns, added, taken, elapsed).
        self.node_added = np.zeros(len(names), np.int64)
        self.node_taken = np.zeros(len(names), np.int64)
        self.node_published: List[Tuple[int, int, int, int, int]] = []
        self.state_replies: set = set()  # keys the node sent a classic state of
        self.node_intervals = 0
        self._by_fd = {p.sock.fileno(): p for p in self.peers}
        self._key_of = {n: i for i, n in enumerate(names)}
        self._poll = select.poll()
        for p in self.peers:
            self._poll.register(p.sock.fileno(), select.POLLIN)
        self.on_tick: Optional[Callable[["PeerSet", int, List[Peer]], None]] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._err: Optional[BaseException] = None
        self.tick_late_ns: List[int] = []
        self.occupancy: List[float] = []
        self.measure_from_ns = None  # occupancy/lateness recorded after this
        self.measure_to_ns = None

    @property
    def addrs(self) -> List[str]:
        return [p.addr for p in self.peers]

    def attach(self, node_port: int, slots: Dict[str, int], node_slot: int) -> None:
        self.node = ("127.0.0.1", node_port)
        self.node_slot = node_slot
        for p in self.peers:
            p.slot = slots[p.addr]

    # -- the loop --------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="perfbench-peers", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError("the peers' thread did not stop")
        for p in self.peers:
            p.sock.close()
        if self._err is not None:
            raise RuntimeError("the peers' thread failed") from self._err

    def check(self) -> None:
        if self._err is not None:
            raise RuntimeError("the peers' thread failed") from self._err

    def _run(self) -> None:
        try:
            for p in self.peers:
                p.sock.sendto(wire.control(wire.ADVERT, wire.DELTA_PACKET_SIZE), self.node)
            next_tick = time.monotonic_ns()
            while not self._stop.is_set():
                now = time.monotonic_ns()
                if now >= next_tick:
                    self._record_tick(now, next_tick)
                    self._tick(now)
                    next_tick += self.flush_ns
                    if next_tick < now:
                        next_tick = now + self.flush_ns
                timeout_ms = max(0, (next_tick - time.monotonic_ns()) // 1_000_000)
                for fd, _ in self._poll.poll(timeout_ms):
                    self._drain_socket(self._by_fd[fd])
        except BaseException as exc:  # reported by check()/stop()
            self._err = exc

    def _record_tick(self, now: int, due: int) -> None:
        lo, hi = self.measure_from_ns, self.measure_to_ns
        if lo is not None and lo <= now and (hi is None or now < hi):
            self.tick_late_ns.append(now - due)
            self.occupancy.append(
                sum(len(p.unacked) for p in self.peers) / (self.window * len(self.peers))
            )

    def _tick(self, now: int) -> None:
        if self.on_tick is not None:
            self.on_tick(self, now, self.peers)
        for p in self.peers:
            if p.node_rx_bound == 0:
                continue  # no advert answer yet: the node's bound is unknown
            for seq, rec in p.unacked.items():
                if now - rec[2] >= rec[4]:
                    p.sock.sendto(rec[0], self.node)
                    rec[2] = now
                    rec[4] = min(2 * rec[4], MAX_RTO_NS)
                    rec[5] = True
                    p.retransmits += 1
            self._ship(p, now)
            while p.pending_acks:
                acks = p.pending_acks[: wire.MAX_ACKS]
                del p.pending_acks[: wire.MAX_ACKS]
                data, _ = wire.encode(p.slot, 0, acks, ())
                p.sock.sendto(data, self.node)

    def _ship(self, p: Peer, now: int) -> None:
        """Ship dirty keys as fresh intervals while the window has room."""
        cap = p.ship_capacity()
        while p.dirty and len(p.unacked) < self.window:
            keys = []
            for k in p.dirty:
                keys.append(k)
                if len(keys) == cap:
                    break
            for k in keys:
                del p.dirty[k]
            idx = np.array(keys, np.int64)
            ent = np.zeros(len(idx), wire.entry_dtype(self.name_len))
            ent["len"] = self.name_len
            ent["name"] = self.names[idx]
            ent["slot"] = p.slot
            ent["cap"] = self.cap_nt
            ent["added"] = p.added[idx]
            ent["taken"] = p.taken[idx]
            ent["elapsed"] = p.elapsed[idx]
            acks = p.pending_acks[: wire.MAX_ACKS]
            del p.pending_acks[: wire.MAX_ACKS]
            seq = p.next_seq
            p.next_seq += 1
            data = wire.encode_fixed(p.slot, seq, acks, ent)
            p.sock.sendto(data, self.node)
            p.unacked[seq] = [data, now, now, len(idx), self._rto(p), False]
            p.shipped.append((now, idx, ent["taken"].astype(np.int64)))
            p.intervals.append((seq, now, len(idx)))

    def _drain_socket(self, p: Peer) -> None:
        while True:
            try:
                data = p.sock.recv(65536)
            except BlockingIOError:
                return
            now = time.monotonic_ns()
            if wire.is_delta(data):
                self._on_delta(p, data, now)
                continue
            ctrl = wire.parse_control(data)
            if ctrl is None:
                state = wire.decode_state(data)
                if state is not None:
                    name, elapsed, lanes = state
                    if name in self._key_of:
                        self.state_replies.add(self._key_of[name])
                    for slot, added, taken in lanes:
                        if slot == self.node_slot:
                            self._node_lane(name, added, taken, elapsed, now)
                continue  # else other control traffic (gossip, audit): not ours
            kind, bound = ctrl
            if kind == wire.PROBE:
                # Answered as a live peer does: a peer that went silent
                # past the node's alive TTL would be "healed" on its next
                # datagram, which drops its interval log and starts an
                # anti-entropy job.
                p.sock.sendto(wire.control(wire.PROBE_ACK), self.node)
                continue
            if kind == wire.PROBE_ACK:
                continue
            if kind == wire.ADVERT:
                p.sock.sendto(wire.control(wire.ADVERT_ACK, wire.DELTA_PACKET_SIZE), self.node)
            p.node_rx_bound = min(bound, wire.DELTA_PACKET_SIZE)

    def _on_delta(self, p: Peer, data: bytes, now: int) -> None:
        pkt = wire.decode(data)
        if pkt is None:
            p.rx_errors += 1
            return
        freed = False
        for seq in pkt.acks:
            rec = p.unacked.pop(seq, None)
            if rec is not None:
                p.acked_at[seq] = now
                freed = True
                if not rec[5]:
                    self._sample_rtt(p, now - rec[1])
        if pkt.seq:
            p.pending_acks.append(pkt.seq)
            self.node_intervals += 1
        for e in pkt.entries:
            if e.slot == self.node_slot:
                self._node_lane(e.name, e.added_nt, e.taken_nt, e.elapsed_ns, now)
        if freed and p.node_rx_bound:
            if self.on_tick is not None and getattr(self.on_tick, "on_ack", False):
                self.on_tick(self, now, [p])
            self._ship(p, now)

    def _node_lane(self, name: bytes, added: int, taken: int, elapsed: int, now: int) -> None:
        """One value of the node's own lane, as the node published it."""
        k = self._key_of.get(name)
        if k is None:
            return
        self.node_published.append((k, now, added, taken, elapsed))
        self.node_added[k] = max(self.node_added[k], added)
        self.node_taken[k] = max(self.node_taken[k], taken)

    def _rto(self, p: Peer) -> int:
        if p.srtt_ns == 0.0:
            return self.retx_ns
        return int(min(max(self.retx_ns, p.srtt_ns + 4 * p.rttvar_ns), MAX_RTO_NS))

    @staticmethod
    def _sample_rtt(p: Peer, rtt: int) -> None:
        if p.srtt_ns == 0.0:
            p.srtt_ns, p.rttvar_ns = float(rtt), rtt / 2.0
        else:
            p.rttvar_ns = 0.75 * p.rttvar_ns + 0.25 * abs(p.srtt_ns - rtt)
            p.srtt_ns = 0.875 * p.srtt_ns + 0.125 * rtt

    # -- lane updates (called from on_tick, on the peers' thread) -------

    def set_lane(self, p: Peer, keys: np.ndarray, added: np.ndarray, taken: np.ndarray,
                 elapsed: Optional[np.ndarray] = None) -> None:
        """Raise ``p``'s lane for ``keys`` to the given absolute values and
        mark them dirty. Values only grow (monotone lanes)."""
        if len(keys) == 0:
            return
        p.added[keys] = np.maximum(p.added[keys], added)
        p.taken[keys] = np.maximum(p.taken[keys], taken)
        if elapsed is not None:
            p.elapsed[keys] = np.maximum(p.elapsed[keys], elapsed)
        for k in keys.tolist():
            p.dirty[k] = None

    def request_states(self, keys: np.ndarray) -> None:
        """Ask the node for the lanes of ``keys`` (incast requests from
        the first peer), as a peer that healed does; the answers arrive
        as classic state packets."""
        p = self.peers[0]
        for k in keys.tolist():
            self.state_replies.discard(k)
            p.sock.sendto(wire.incast_request(bytes(self.names[k]), p.slot), self.node)

    # -- queries (safe from the main thread between phases) -------------

    def all_answered(self) -> bool:
        return all(p.node_rx_bound for p in self.peers)

    def idle(self) -> bool:
        """Nothing dirty, nothing unacknowledged, nothing left to ack."""
        return all(not p.dirty and not p.unacked and not p.pending_acks for p in self.peers)
