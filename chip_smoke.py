#!/usr/bin/env python3
"""Chip smoke: boot the real server on the chip and prove its answers.

``python chip_smoke.py`` drives ``python -m patrol_tpu`` (``cli.main``) at
the north-star shape — 1M buckets x 256 PN lanes, native HTTP front,
native UDP replication, warm-up on, ``--platform tpu`` — and checks every
answer against a plain host reference:

* device   the backend the server reports; anything but a TPU fails;
* boot     warm-up seconds, compile-cache directory, selected kernels;
* load     seeded v2 delta datagrams (wire codec) for ~100k buckets over
           many of the 256 lanes, read back with ``GET /tokens`` and
           compared with the per-lane max join computed here;
* serve    seeded ``/take`` and ``/take_batch`` traffic on device-resident
           and host-resident buckets, plus a pipelined blast that promotes
           one bucket onto the device take kernel; every status and
           remaining count against ``SequentialSpec``. Rates refill less
           than one token during the run, so outcomes do not depend on
           the clock;
* refill   a seeded grid through the device take kernel at the served
           state shape, bit for bit against the host twin;
* proof    ``device_take_ns``/``device_commit_ns`` counts (0 fails), peak
           device bytes, comparisons and mismatches.

This parent never touches a JAX backend: the server is the one child that
holds the chip, then the refill child holds it alone. ``--chips 4`` runs
only the mesh path (replicas=1 at 4M x 256, replicas=2 at 1M x 256) with
its load/serve comparison, per-device shard bytes and fused-dispatch
count. The last stdout line is the contract JSON, printed only on success.
``--rehearse`` runs every phase on whatever backend ``JAX_PLATFORMS``
names at a size you pick, and always exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import random
import signal
import socket
import subprocess
import sys
import time
from urllib.parse import quote

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")  # + "-rehearsal" on --rehearse
PERIOD = "100000h"  # with freq <= 1e5: an interval of >= 1 h per token
NANO = 10**9


class SmokeFailure(Exception):
    pass


def phase(name: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"{name}: {body}", flush=True)


def free_port(kind: int) -> int:
    with socket.socket(socket.AF_INET, kind) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# The server child.


class Server:
    def __init__(self, args, label: str, buckets: int, lanes: int, extra=()):
        self.api_port = free_port(socket.SOCK_STREAM)
        self.node_port = free_port(socket.SOCK_DGRAM)
        # The driver is the server's one peer: the server acknowledges
        # its delta intervals (acks go to configured peers only).
        self.peer = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.peer.bind(("127.0.0.1", 0))
        self.peer.settimeout(0.02)
        self.seq = 1  # the driver's next delta interval number
        out_dir = OUT_DIR + ("-rehearsal" if args.rehearse else "")
        os.makedirs(out_dir, exist_ok=True)
        self.log_path = os.path.join(out_dir, f"server-{label}.log")
        cmd = [
            sys.executable, "-m", "patrol_tpu",
            "--platform", args.platform,
            "--buckets", str(buckets), "--node-lanes", str(lanes),
            "--http-front", "native", "--udp-backend", "native",
            "--api-addr", f"127.0.0.1:{self.api_port}",
            "--node-addr", f"127.0.0.1:{self.node_port}",
            "--peer-addr", f"127.0.0.1:{self.peer.getsockname()[1]}",
            *extra,
        ]
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, cwd=REPO, stdout=self._log, stderr=subprocess.STDOUT
        )
        self.conn = None

    def wait_ready(self, timeout_s: float) -> dict:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"server exited rc={self.proc.returncode} during boot:\n"
                    + self.log_tail()
                )
            try:
                return self.vars()
            except (OSError, http.client.HTTPException):
                self.conn = None
                time.sleep(0.5)
        raise SmokeFailure(f"server not serving after {timeout_s:.0f}s:\n" + self.log_tail())

    def request(self, method: str, path: str):
        if self.conn is None:
            self.conn = http.client.HTTPConnection("127.0.0.1", self.api_port, timeout=120)
        self.conn.request(method, path)
        resp = self.conn.getresponse()
        return resp.status, resp.read()

    def vars(self) -> dict:
        status, body = self.request("GET", "/debug/vars")
        if status != 200:
            raise SmokeFailure(f"/debug/vars answered {status}")
        return json.loads(body)

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(errors="replace")

    def stop(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        self.peer.close()


# ---------------------------------------------------------------------------
# Load: seeded delta datagrams, the per-lane max join as the reference.


def make_load(rng: random.Random, n_buckets: int, lanes):
    """→ (entries to send, {name: (freq, joined whole-token balance)}).
    Every lane value is a whole number of tokens. Lanes carry positive
    and negative balances alike — a lane may hold more refill grants
    than spend — so a partial join, read before the spending lanes land,
    can sit at or above capacity; only the whole join is in [0, freq).
    Half the lanes also ship an older interval that the final one
    dominates. Rates are ``freq:PERIOD``, so no take sees a token of
    refill during the run."""
    from patrol_tpu.ops import wire

    entries, ref = [], {}
    for i in range(n_buckets):
        name = f"ld{i:06d}"
        freq = rng.randint(16, 4096)
        k = rng.randint(1, 4)
        slots = rng.sample(lanes, k)
        balance = rng.randint(0, freq - 1)
        nets = [rng.randint(-freq, freq) for _ in range(k - 1)]
        nets.append(balance - freq - sum(nets))
        elapsed = rng.randint(0, 2**40)
        for s, net in zip(slots, nets):
            a = max(net, 0) + rng.randint(0, freq)
            t = a - net
            entries.append(wire.DeltaEntry(name, s, freq * NANO, a * NANO, t * NANO, elapsed))
            if rng.random() < 0.5:  # an older interval, dominated by the final
                entries.append(
                    wire.DeltaEntry(
                        name, s, freq * NANO, rng.randint(0, a) * NANO,
                        rng.randint(0, t) * NANO, rng.randint(0, elapsed),
                    )
                )
        ref[name] = (freq, balance)
    rng.shuffle(entries)
    return entries, ref


def send_load(server: Server, entries, window: int = 32) -> tuple:
    """Ship ``entries`` as v2 delta intervals the way a peer's delta plane
    does: at most ``window`` intervals unacknowledged, each retransmitted
    (same seq, same absolute values — a no-op if it did land) until the
    server's ack vector names it. → (datagrams, retransmits)."""
    from collections import deque

    from patrol_tpu.ops import wire

    packets, i = {}, 0
    while i < len(entries):
        data, n = wire.encode_delta_packet(1, server.seq, (), entries[i:])
        packets[server.seq] = data
        server.seq, i = server.seq + 1, i + n
    todo, unacked, retx = deque(packets), {}, 0
    dest, deadline = ("127.0.0.1", server.node_port), time.time() + 600
    while todo or unacked:
        if time.time() > deadline:
            raise SmokeFailure(f"{len(unacked) + len(todo)} intervals never acknowledged")
        now = time.time()
        for q, sent in list(unacked.items()):
            if now - sent > 0.5:
                server.peer.sendto(packets[q], dest)
                unacked[q] = now
                retx += 1
        while todo and len(unacked) < window:
            q = todo.popleft()
            server.peer.sendto(packets[q], dest)
            unacked[q] = now
        try:
            data = server.peer.recv(65536)
        except socket.timeout:
            continue
        pkt = wire.decode_delta_packet(data)
        for q in pkt.acks if pkt is not None else ():
            unacked.pop(q, None)
    return len(packets), retx


def wait_quiet(server: Server, quiet_s: float = 2.0) -> dict:
    """Until the server's rx and tick counters have held still for
    ``quiet_s`` with nothing queued, no tick applying (a tick compiling a
    new shape holds the counters still for as long as the compile takes)
    and no completion pending."""
    keys = ("wire_delta_rx_deltas", "engine_ticks", "engine_pending_completions")
    last, since, deadline = None, time.time(), time.time() + 600
    while time.time() < deadline:
        v = server.vars()
        now = tuple(v.get(k) for k in keys)
        idle = not (
            v["engine_backlog"] or v["engine_tick_busy"] or v["engine_pending_completions"]
        )
        if now != last or not idle:
            last, since = now, time.time()
        elif time.time() - since >= quiet_s:
            return v
        time.sleep(0.25)
    raise SmokeFailure("the server did not settle within 600 s")


def check_load(server: Server, ref: dict, rng: random.Random, sample: int) -> tuple:
    names = rng.sample(sorted(ref), min(sample, len(ref)))
    bad = []
    for name in names:
        status, body = server.request("GET", f"/tokens/{name}")
        want = ref[name][1]
        if status != 200 or int(body) != want:
            bad.append((name, status, body.decode(errors="replace"), want))
    if bad:
        # Read each miss again later: an answer that has caught up by then
        # was read too early; one that has not was lost.
        time.sleep(5.0)
        bad = [(*b, server.request("GET", f"/tokens/{b[0]}")[1].decode()) for b in bad]
    return len(names), bad


# ---------------------------------------------------------------------------
# Serve: takes against SequentialSpec.


def serve_phase(server: Server, ref: dict, rng: random.Random, n: int) -> tuple:
    from patrol_tpu.analysis.linearizability import SequentialSpec

    specs = {}

    def spec(name: str, freq: int, balance: int) -> SequentialSpec:
        if name not in specs:
            sp = SequentialSpec(freq)
            sp.tokens = balance
            specs[name] = sp
        return specs[name]

    compared, bad = 0, []

    def expect(name, freq, balance, count):
        sp = spec(name, freq, balance)
        ok = sp.take(count)
        return (200 if ok else 429), sp.tokens

    loaded = rng.sample(sorted(ref), min(n, len(ref)))
    fresh = [(f"fr{i:05d}", rng.randint(1, 50)) for i in range(n)]
    # Single takes: device-resident (replicated-in) and host-resident (new).
    for name in loaded:
        freq, balance = ref[name]
        for _ in range(rng.randint(1, 3)):
            count = rng.randint(1, max(1, balance // 2 + 2))
            status, body = server.request(
                "POST", f"/take/{name}?rate={freq}:{PERIOD}&count={count}"
            )
            want = expect(name, freq, balance, count)
            compared += 1
            if (status, int(body)) != want:
                bad.append(("take", name, count, status, body, want))
    for name, freq in fresh:
        for _ in range(rng.randint(1, 6)):
            count = rng.randint(1, freq)
            status, body = server.request(
                "POST", f"/take/{name}?rate={freq}:{PERIOD}&count={count}"
            )
            want = expect(name, freq, freq, count)
            compared += 1
            if (status, int(body)) != want:
                bad.append(("take", name, count, status, body, want))
    # Batches mixing both kinds, with repeated names inside one request.
    # A repeated name keeps one count per batch: the take-fold coalesces
    # a device row's same-(rate, count) entries, so a batch interleaving
    # counts on one bucket applies them grouped by count (a valid
    # linearization of concurrent entries, not request order).
    pool = [(nm, *ref[nm]) for nm in loaded[: max(1, n // 4)]]
    pool += [(f"tb{i:04d}", f, f) for i, f in enumerate(rng.randint(1, 40) for _ in range(n // 4 + 1))]
    for _ in range(max(1, n // 5)):
        batch = [rng.choice(pool) for _ in range(rng.randint(2, 24))]
        per_name = {nm: rng.randint(1, 8) for nm, _, _ in batch}
        counts = [per_name[nm] for nm, _, _ in batch]
        query = "&".join(
            f"t={quote(nm)},{f}:{PERIOD},{c}" for (nm, f, _), c in zip(batch, counts)
        )
        status, body = server.request("POST", f"/take_batch?{query}")
        lines = body.decode().split("\n")[: len(batch)]
        for (nm, f, bal), c, line in zip(batch, counts, lines):
            want = expect(nm, f, bal, c)
            compared += 1
            got = tuple(int(x) for x in line.split(" ")) if status == 200 else (status, -1)
            if got != want:
                bad.append(("batch", nm, c, status, line, want))
    return compared, bad


def promote_phase(server: Server, ms: int) -> tuple:
    """Promote one host-resident bucket onto the device the way a cluster
    does — replication pressure past the per-window threshold on a bucket
    this node serves in-front — then blast it with the native pipelined
    client, whose takes now run on the device take kernel. The reference
    admits min(balance, requests) and leaves the rest."""
    import numpy as np

    from patrol_tpu import native
    from patrol_tpu.ops import wire

    cap, name = 200_000, "hot0"
    status, body = server.request("POST", f"/take/{name}?rate={cap}:{PERIOD}&count=1")
    bad = [] if (status, int(body)) == (200, cap - 1) else [("first-take", status, body)]
    # Three bursts of per-lane monotone entries: whole-token spends, each
    # burst well past the 4096-per-window promotion threshold.
    spend = {}
    for burst in range(3):
        entries = []
        for i in range(8000):
            slot = server.lanes[i % len(server.lanes)]
            spend[slot] = spend.get(slot, 0) + 1
            entries.append(wire.DeltaEntry(name, slot, cap * NANO, 0, spend[slot] * NANO, 0))
        send_load(server, entries)
    wait_quiet(server, quiet_s=1.0)
    balance = cap - 1 - sum(spend.values())
    lib = native.load()
    if lib is None:
        raise SmokeFailure("native library did not load in the driver")
    out = np.zeros(5, np.uint64)
    target = f"/take/{name}?rate={cap}:{PERIOD}".encode()
    rc = lib.pt_http_blast(b"127.0.0.1", server.api_port, target, 4, 64, ms, out)
    if rc != 0:
        raise SmokeFailure(f"pt_http_blast rc={rc}")
    done, ok, limited = int(out[0]), int(out[3]), int(out[4])
    want_ok = min(balance, done)
    status, body = server.request("GET", f"/tokens/{name}")
    got_tokens = int(body) if status == 200 else None
    if ok != want_ok or ok + limited != done:
        bad.append(("blast", done, ok, limited, want_ok))
    if got_tokens != balance - want_ok:
        bad.append(("blast-tokens", got_tokens, balance - want_ok))
    return done, ok, bad


# ---------------------------------------------------------------------------
# Refill exactness, in a child that holds the chip alone.


def refill_grid(k: int, seed: int):
    """Seeded (delta, freq, per_ns) rows: intervals that do not divide,
    deltas near 2^53 ns and far beyond, per/freq truncation, degenerate
    rates."""
    import numpy as np

    rng = np.random.default_rng(seed)
    freq = rng.integers(1, 10**6, k)
    per = rng.integers(10**6, 3600 * 10**12, k)
    q = k // 8
    per[:q] = freq[:q] * rng.integers(1, 10**9, q) + rng.integers(1, 1000, q)
    interval = np.maximum(per // freq, 1)
    delta = rng.integers(0, 2**40, k)
    delta[q : 2 * q] = 2**53 + rng.integers(-4096, 4096, q)
    delta[2 * q : 3 * q] = rng.integers(2**52, 2**62, q)
    delta[3 * q : 4 * q] = interval[3 * q : 4 * q] * rng.integers(0, 10**6, q) + rng.integers(-3, 4, q)
    delta[4 * q : 5 * q] = rng.integers(0, 2**20, q)
    per[4 * q : 5 * q] = rng.integers(1, 2**12, q)
    freq[4 * q : 5 * q] = rng.integers(1, 2**6, q)
    freq[-8:] = [0, 1, 3, 7, 10**6, 1, 2, 5]
    per[-8:] = [10**9, 0, 2, 10**15, 999_999, 1, 2**62, 3]
    return np.maximum(delta, 0), freq, per


def refill_child(args) -> None:
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    import jax.numpy as jnp
    import numpy as np

    import patrol_tpu  # noqa: F401  (x64)
    from patrol_tpu.models.limiter import LimiterConfig, init_state
    from patrol_tpu.ops.take import TAKE_PACK_ROWS, host_refill_grant_nt
    from patrol_tpu.runtime.engine import MAX_TAKE_ROWS, _jit_take_packed
    from patrol_tpu.utils import compile_cache

    compile_cache.enable()
    k = MAX_TAKE_ROWS  # the warmed variant: the served executable itself
    delta, freq, per = refill_grid(k, args.seed)
    state = init_state(LimiterConfig(buckets=args.buckets, nodes=args.node_lanes))
    # A deep deficit on lane 1 keeps the capacity clamp out of the way:
    # have = −2^62 + grant exposes the raw grant of every row.
    deficit = jnp.int64(2**62)
    state = state._replace(pn=state.pn.at[:k, 1, 1].set(deficit))
    packed = np.zeros((TAKE_PACK_ROWS, k), np.int64)
    packed[0] = np.arange(k)
    packed[1] = delta  # now; created = elapsed = 0, so delta = now
    packed[2] = freq
    packed[3] = per
    packed[4] = NANO
    packed[5] = 1
    _, out = _jit_take_packed(0)(state, jnp.asarray(packed))
    dev = np.asarray(out)[0] + 2**62
    host = np.array(
        [
            min(host_refill_grant_nt(int(d), int(f), int(p)), int(f) * NANO + 2**62)
            for d, f, p in zip(delta, freq, per)
        ],
        np.int64,
    )
    miss = np.flatnonzero(dev != host)
    print(json.dumps({
        "rows": int(k),
        "mismatches": int(miss.size),
        "examples": [
            [int(delta[i]), int(freq[i]), int(per[i]), int(dev[i]), int(host[i])]
            for i in miss[:5]
        ],
        "platform": jax.devices()[0].platform,
    }), flush=True)


def run_refill(args) -> dict:
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", "refill",
        "--platform", args.platform, "--buckets", str(args.buckets),
        "--node-lanes", str(args.node_lanes), "--seed", str(args.seed),
    ]
    r = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True, timeout=900)
    if r.returncode != 0 or not r.stdout.strip():
        raise SmokeFailure(f"refill child rc={r.returncode}:\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Phases in order.


def boot(args, label, buckets, lanes, extra=()) -> tuple:
    server = Server(args, label, buckets, lanes, extra)
    try:
        v = server.wait_ready(args.boot_timeout)
    except BaseException:
        server.stop()
        raise
    dev = v["device"]
    # Every lane but the server's own carries the driver's replication.
    server.lanes = [lane for lane in range(lanes) if lane != v["node_slot"]]
    phase(
        f"device[{label}]", platform=dev["platform"], kind=repr(dev["kind"]),
        count=dev["count"], jax=dev["jax"],
    )
    if dev["platform"] != "tpu" and not args.rehearse:
        server.stop()
        raise SmokeFailure(f"platform is {dev['platform']}, not tpu")
    phase(
        f"boot[{label}]", warmup_s=dev["warmup_s"],
        compile_cache=dev["compile_cache_dir"],
        ingest_kernel=repr(dev["ingest_kernel"]), buckets=buckets, lanes=lanes,
    )
    return server, dev


def load_and_serve(args, server, label, rng) -> int:
    """Load + serve against one booted server; → mismatches."""
    entries, ref = make_load(rng, args.load_buckets, server.lanes)
    t0 = time.time()
    n_packets, retx = send_load(server, entries)
    v = wait_quiet(server)
    n, bad = check_load(server, ref, rng, args.sample)
    phase(
        f"load[{label}]", buckets=len(ref), entries=len(entries), datagrams=n_packets,
        retransmits=retx, rx_deltas=v.get("wire_delta_rx_deltas"), bound=v.get("buckets"),
        gc_reclaimed=v.get("engine_gc_reclaimed"), seconds=time.time() - t0,
        compared=n, mismatches=len(bad),
    )
    for b in bad[:5]:
        print(f"  load mismatch: {b}", flush=True)
    compared, sbad = serve_phase(server, ref, rng, args.serve)
    phase(f"serve[{label}]", compared=compared, mismatches=len(sbad))
    for b in sbad[:5]:
        print(f"  serve mismatch: {b}", flush=True)
    return len(bad) + len(sbad)


def device_counts(v: dict) -> tuple:
    h = v.get("histograms", {})
    return (
        h.get("device_take_ns", {}).get("count", 0),
        h.get("device_commit_ns", {}).get("count", 0),
    )


def check_faults(server: Server, v: dict, label: str) -> None:
    """Fail on any engine tick (or mesh sub-dispatch) that raised: its
    work was lost with it, whatever the comparisons say."""
    n = v.get("engine_tick_failures", 0)
    phase(f"faults[{label}]", engine_tick_failures=n)
    if n:
        raise SmokeFailure(f"{n} engine ticks failed:\n" + server.log_tail(80))


def one_chip(args) -> dict:
    rng = random.Random(args.seed)
    server, dev = boot(args, "1chip", args.buckets, args.node_lanes)
    try:
        mism = load_and_serve(args, server, "1chip", rng)
        done, ok, pbad = promote_phase(server, args.blast_ms)
        v = wait_quiet(server)
        promotions = v.get("engine_promotions", 0)
        phase(
            "promote", requests=done, admitted=ok, promotions=promotions,
            mismatches=len(pbad),
        )
        for b in pbad:
            print(f"  promote mismatch: {b}", flush=True)
        mism += len(pbad)
        if not promotions:
            raise SmokeFailure("the blast promoted no bucket onto the device path")
        takes, commits = device_counts(v)
        peak = v["device"]["peak_bytes_in_use"]
        check_faults(server, v, "1chip")
    finally:
        server.stop()
    refill = run_refill(args)
    phase(
        "refill", rows=refill["rows"], mismatches=refill["mismatches"],
        examples=refill["examples"],
    )
    mism += refill["mismatches"]
    phase(
        "proof", device_take_ns_count=takes, device_commit_ns_count=commits,
        peak_bytes_in_use=peak, mismatches=mism,
    )
    if not takes or not commits:
        raise SmokeFailure("no take or no commit ran on the device")
    if mism:
        raise SmokeFailure(f"{mism} answers differ from the reference")
    return dev


def four_chips(args) -> dict:
    rng = random.Random(args.seed)
    dev, mism = None, 0
    # Two replicas at 2M x 256 need 19.08 GiB a chip (the tree converge
    # holds four shard-sized temporaries): they run at 1M x 256.
    for replicas, buckets in ((1, 4 * args.buckets), (2, args.buckets)):
        label = f"mesh-r{replicas}"
        # No warm-up: four chips cost four times as much per second, and
        # the mesh compiles only the few variants this traffic reaches.
        server, dev = boot(
            args, label, buckets, args.node_lanes,
            ("--mesh-replicas", str(replicas), "--no-warmup"),
        )
        try:
            mism += load_and_serve(args, server, label, rng)
            v = wait_quiet(server)
            shard_bytes = v.get("mesh_shard_bytes", {})
            fused = v.get("mesh_fused_dispatches", 0)
            peak = v["device"]["peak_bytes_in_use"]
            check_faults(server, v, label)
        finally:
            server.stop()
        shards = v.get("mesh_shards", 1)
        want = buckets // shards * (args.node_lanes * 2 + 1) * 8  # pn + elapsed
        phase(
            f"mesh[{label}]", shards=v.get("mesh_shards"), replicas=v.get("mesh_replicas"),
            shard_bytes=shard_bytes, want_each=want, fused_dispatches=fused,
            peak_bytes_in_use=peak,
        )
        if len(shard_bytes) != dev["count"] or any(b != want for b in shard_bytes.values()):
            raise SmokeFailure(f"{label}: a device does not hold its share of the state")
        if not fused:
            raise SmokeFailure(f"{label}: mesh_fused_dispatches is 0")
    phase("proof", mismatches=mism)
    if mism:
        raise SmokeFailure(f"{mism} answers differ from the reference")
    return dev


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--buckets", type=int, default=1_000_000)
    p.add_argument("--node-lanes", type=int, default=256)
    p.add_argument("--load-buckets", type=int, default=100_000)
    p.add_argument("--sample", type=int, default=2000, help="GET /tokens read-backs")
    p.add_argument("--serve", type=int, default=300, help="buckets per serve kind")
    p.add_argument("--blast-ms", type=int, default=1500)
    p.add_argument("--seed", type=int, default=21)
    p.add_argument("--boot-timeout", type=float, default=900.0)
    p.add_argument("--rehearse", action="store_true",
                   help="run on the JAX_PLATFORMS backend; never prints a result")
    p.add_argument("--platform", default=None, help=argparse.SUPPRESS)
    p.add_argument("--child", choices=("refill",), help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if args.child == "refill":
        refill_child(args)
        return 0
    env_platforms = os.environ.get("JAX_PLATFORMS", "")
    if args.rehearse:
        args.platform = env_platforms.split(",")[0] or "cpu"
    else:
        if env_platforms and "tpu" not in env_platforms.split(","):
            print(f"device: JAX_PLATFORMS={env_platforms} leaves no tpu", flush=True)
            return 1
        args.platform = "tpu"
    try:
        dev = four_chips(args) if args.chips == 4 else one_chip(args)
    except SmokeFailure as exc:
        print(f"FAILED: {exc}", flush=True)
        return 1
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        print("FAILED: the driver process initialized a JAX backend", flush=True)
        return 1
    if args.rehearse or dev["platform"] != "tpu":
        print(f"rehearsal on {dev['platform']}: all phases passed; no result", flush=True)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"]},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
