"""The system under test: one node process that holds the chip.

The benchmark's own process never initialises a JAX backend; the node
is its one child that does. The node is started from the checkout as
``python -m patrol_tpu`` with the configuration's flags, and everything
the benchmark reads from it comes over HTTP: ``/debug/vars`` (counters,
histograms, the ``device`` block), ``/tokens``, ``/debug/jax/trace``.
"""

from __future__ import annotations

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NodeError(RuntimeError):
    pass


def free_port(kind: int) -> int:
    with socket.socket(socket.AF_INET, kind) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Node:
    def __init__(
        self,
        node_cfg: dict,
        peer_addrs: List[str],
        platform: str,
        log_path: str,
        trace: bool,
        launcher: Optional[List[str]] = None,
    ):
        self.api_port = free_port(socket.SOCK_STREAM)
        self.node_port = free_port(socket.SOCK_DGRAM)
        self.node_addr = f"127.0.0.1:{self.node_port}"
        self.log_path = log_path
        cmd = list(launcher or [sys.executable, "-m", "patrol_tpu"])
        cmd += [
            "--platform", platform,
            "--buckets", str(node_cfg["buckets"]),
            "--node-lanes", str(node_cfg["node_lanes"]),
            "--http-front", node_cfg["http_front"],
            "--udp-backend", node_cfg["udp_backend"],
            "--api-addr", f"127.0.0.1:{self.api_port}",
            "--node-addr", self.node_addr,
        ]
        for a in peer_addrs:
            cmd += ["--peer-addr", a]
        if not node_cfg.get("warmup", True):
            cmd.append("--no-warmup")
        env = dict(os.environ)
        env.update({k: str(v) for k, v in node_cfg.get("env", {}).items()})
        # Every compile is logged, so the benchmark can count those that
        # fall inside the measured window.
        env["JAX_LOG_COMPILES"] = "1"
        if trace:
            env["PATROL_DEVICE_ANNOTATIONS"] = "1"
        self._log = open(log_path, "wb")
        self.proc = subprocess.Popen(
            cmd, cwd=CHECKOUT, stdout=self._log, stderr=subprocess.STDOUT, env=env
        )
        self._conn: Optional[http.client.HTTPConnection] = None

    # -- HTTP ------------------------------------------------------------

    def request(self, method: str, path: str, timeout: float = 120.0):
        if self._conn is None:
            self._conn = http.client.HTTPConnection("127.0.0.1", self.api_port, timeout=timeout)
        try:
            self._conn.request(method, path)
            resp = self._conn.getresponse()
            return resp.status, resp.read()
        except (OSError, http.client.HTTPException):
            self._conn.close()
            self._conn = None
            raise

    def vars(self) -> dict:
        status, body = self.request("GET", "/debug/vars")
        if status != 200:
            raise NodeError(f"/debug/vars answered {status}")
        return json.loads(body)

    def wait_ready(self, timeout_s: float) -> dict:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise NodeError(f"node exited rc={self.proc.returncode} during boot:\n{self.log_tail()}")
            try:
                return self.vars()
            except (OSError, http.client.HTTPException):
                time.sleep(0.25)
        raise NodeError(f"node not serving after {timeout_s:.0f}s:\n{self.log_tail()}")

    def wait_quiet(self, quiet_s: float, timeout_s: float, extra=lambda: True) -> dict:
        """Until nothing is queued or applying, and the rx and tick
        counters (and ``extra()``) have held still for ``quiet_s``."""
        keys = ("wire_delta_rx_deltas", "engine_ticks", "wire_deltas_batched")
        last, since = None, time.monotonic()
        deadline = since + timeout_s
        while time.monotonic() < deadline:
            v = self.vars()
            now = tuple(v.get(k) for k in keys)
            busy = (
                v.get("engine_backlog") or v.get("engine_tick_busy")
                or v.get("engine_pending_completions") or v.get("wire_intervals_unacked")
                or not extra()
            )
            if now != last or busy:
                last, since = now, time.monotonic()
            elif time.monotonic() - since >= quiet_s:
                return v
            time.sleep(0.1)
        raise NodeError(f"node did not settle within {timeout_s:.0f}s")

    def trace(self, seconds: float) -> str:
        """Capture the device trace for ``seconds``; → its directory."""
        status, body = self.request("GET", f"/debug/jax/trace?seconds={seconds:g}", timeout=seconds + 120)
        text = body.decode(errors="replace")
        if status != 200 or " to " not in text:
            raise NodeError(f"/debug/jax/trace answered {status}: {text[:200]}")
        return text.split(" to ", 1)[1].split("\n", 1)[0].strip()

    def tokens(self, names: List[str]) -> Dict[str, Optional[int]]:
        out: Dict[str, Optional[int]] = {}
        for name in names:
            status, body = self.request("GET", f"/tokens/{name}")
            out[name] = int(body) if status == 200 else None
        return out

    # -- the process -----------------------------------------------------

    def log_size(self) -> int:
        self._log.flush()
        return os.path.getsize(self.log_path)

    def log_slice(self, lo: int, hi: int) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(lo)
            return f.read(max(0, hi - lo)).decode(errors="replace")

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            return b"\n".join(f.read().splitlines()[-n:]).decode(errors="replace")

    def stop(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()


def count_compiles(log_text: str) -> int:
    """Compiles JAX logged (``JAX_LOG_COMPILES``) in a slice of the log."""
    return sum(1 for line in log_text.splitlines() if "Compiling " in line)


def device_of(v: dict) -> dict:
    dev = v["device"]
    peaks = [b for b in (dev.get("peak_bytes_in_use") or []) if b is not None]
    return {
        "platform": dev["platform"],
        "kind": dev["kind"],
        "count": int(dev["count"]),
        "memory_peak_bytes": max(peaks) if peaks else None,
    }
