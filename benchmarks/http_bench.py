"""End-to-end HTTP benchmarks — the vegeta-equivalent tier the reference's
test suite gestures at (command_test.go:79-107) but never measures
(BASELINE.md: no published numbers).

Covers the first two BASELINE.json configs end-to-end over real sockets:

  1. single node, one bucket, ``POST /take?rate=100:1s&count=1`` —
     closed-loop latency distribution (p50/p90/p99) + throughput;
  2. 3-node loopback cluster, 10k buckets, zipf(0.99) key distribution —
     cluster-wide throughput with replication running.

Prints one JSON line per config. Runs on CPU by default (the HTTP path is
host-bound; set PATROL_HTTP_BENCH_PLATFORM=tpu to exercise the device).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The HTTP path is host-bound; default to CPU regardless of the
# environment's platform (set PATROL_HTTP_BENCH_PLATFORM to change), pinned
# in jax's config as tests/conftest.py does.
os.environ["JAX_PLATFORMS"] = os.environ.get("PATROL_HTTP_BENCH_PLATFORM", "cpu")

import jax

jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])

import asyncio
import socket
import threading
import time

import numpy as np


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Node:
    """One Command stack on a background event loop."""

    def __init__(
        self, api_port, node_port, peers, buckets=16384, lanes=8, front="python"
    ):
        from patrol_tpu.command import Command
        from patrol_tpu.models.limiter import LimiterConfig

        self.cmd = Command(
            api_addr=f"127.0.0.1:{api_port}",
            node_addr=f"127.0.0.1:{node_port}",
            peer_addrs=peers,
            shutdown_timeout_s=5.0,
            config=LimiterConfig(buckets=buckets, nodes=lanes),
            handle_signals=False,
            warmup=True,
            http_front=front,
        )
        self.api_port = api_port
        self.loop = asyncio.new_event_loop()
        self.stop_event = None
        self._ready = threading.Event()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()
        assert self._ready.wait(60)
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                socket.create_connection(("127.0.0.1", self.api_port), timeout=1).close()
                return
            except OSError:
                time.sleep(0.1)
        raise RuntimeError("API never came up")

    def _run(self):
        asyncio.set_event_loop(self.loop)

        async def main():
            self.stop_event = asyncio.Event()
            task = asyncio.ensure_future(self.cmd.run(self.stop_event))
            await asyncio.sleep(0.3)
            self._ready.set()
            await task

        self.loop.run_until_complete(main())

    def close(self):
        self.loop.call_soon_threadsafe(self.stop_event.set)
        self.thread.join(timeout=10)


class Worker(threading.Thread):
    """Closed-loop keep-alive client: fire, await, repeat."""

    def __init__(self, port, targets, stop_at):
        super().__init__(daemon=True)
        self.port = port
        self.targets = targets
        self.stop_at = stop_at
        self.latencies = []
        self.ok = 0
        self.limited = 0

    def run(self):
        sock = socket.create_connection(("127.0.0.1", self.port), timeout=5)
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        buf = b""
        i = 0
        while time.perf_counter() < self.stop_at:
            target = self.targets[i % len(self.targets)]
            i += 1
            req = f"POST {target} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
            t0 = time.perf_counter()
            sock.sendall(req)
            # Read one response (headers + content-length body).
            while b"\r\n\r\n" not in buf:
                buf += sock.recv(65536)
            head, _, rest = buf.partition(b"\r\n\r\n")
            clen = 0
            for line in head.split(b"\r\n"):
                if line.lower().startswith(b"content-length:"):
                    clen = int(line.split(b":")[1])
            while len(rest) < clen:
                rest += sock.recv(65536)
            buf = rest[clen:]
            self.latencies.append(time.perf_counter() - t0)
            status = int(head.split(b" ", 2)[1])
            if status == 200:
                self.ok += 1
            elif status == 429:
                self.limited += 1
        sock.close()


def run_load(ports, targets, duration_s, workers):
    stop_at = time.perf_counter() + duration_s
    ws = [
        Worker(ports[w % len(ports)], targets[w::workers] or targets, stop_at)
        for w in range(workers)
    ]
    t0 = time.perf_counter()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    wall = time.perf_counter() - t0
    lats = np.array(sorted(l for w in ws for l in w.latencies))
    total = len(lats)
    return {
        "requests": total,
        "throughput_rps": round(total / wall),
        "ok": sum(w.ok for w in ws),
        "limited": sum(w.limited for w in ws),
        "p50_us": round(float(np.percentile(lats, 50)) * 1e6),
        "p90_us": round(float(np.percentile(lats, 90)) * 1e6),
        "p99_us": round(float(np.percentile(lats, 99)) * 1e6),
        "max_us": round(float(lats[-1]) * 1e6),
    }


def config1(duration_s=3.0, workers=8, front="python"):
    api, node = free_port(), free_port()
    n = Node(api, node, [], front=front)
    try:
        # Warmup (first take compiles the kernel variants).
        run_load([api], ["/take/warm?rate=100:1s"], 0.5, 2)
        out = run_load([api], ["/take/hot?rate=100:1s&count=1"], duration_s, workers)
        out["config"] = "1: single node, 1 bucket, rate=100:1s"
        out["front"] = front
        return out
    finally:
        n.close()


def config2(duration_s=3.0, workers=12, keys=10_000, zipf_s=0.99, front="python"):
    api_ports = [free_port() for _ in range(3)]
    node_ports = [free_port() for _ in range(3)]
    peers = [f"127.0.0.1:{p}" for p in node_ports]
    nodes = [
        Node(api_ports[i], node_ports[i], peers, front=front) for i in range(3)
    ]
    try:
        rng = np.random.default_rng(7)
        weights = 1.0 / np.arange(1, keys + 1) ** zipf_s
        weights /= weights.sum()
        sample = rng.choice(keys, size=4096, p=weights)
        targets = [f"/take/k{z}?rate=10:1s" for z in sample]
        run_load(api_ports, targets[:64], 0.5, 3)  # warmup
        out = run_load(api_ports, targets, duration_s, workers)
        out["config"] = "2: 3-node cluster, 10k buckets, zipf-0.99"
        out["front"] = front
        return out
    finally:
        for n in nodes:
            n.close()


def _fronts():
    from patrol_tpu import native

    return ["python", "native"] if native.load() is not None else ["python"]


def main():
    duration = float(os.environ.get("PATROL_HTTP_BENCH_SECONDS", "3"))
    workers = int(os.environ.get("PATROL_HTTP_BENCH_WORKERS", "8"))
    for front in _fronts():
        print(json.dumps(config1(duration, workers=workers, front=front)), flush=True)
    for front in _fronts():
        print(
            json.dumps(config2(duration, workers=max(workers, 12), front=front)),
            flush=True,
        )


if __name__ == "__main__":
    sys.exit(main())
