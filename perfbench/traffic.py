"""The one general traffic generator: schedules and key laws from a seed.

A traffic mix is a data file (``traffic/<name>.json``) of parameters; a
configuration (``configs/<name>.json``) fixes the key population and the
bucket rate. Everything drawn here comes from ``numpy``'s PCG64 seeded by
the run's ``--seed`` and a fixed stream label, so the same seed gives the
same schedule, keys and values, and different streams never share draws.
"""

from __future__ import annotations

import zlib
from typing import Dict

import numpy as np

# Every seed gets the same law over the same population; what changes is
# the draw (and, for skewed laws, which names are the hot ones).


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator for one named stream of one run."""
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), zlib.crc32(stream.encode())])


def key_names(config: dict) -> list:
    fmt = config["key_format"]
    return [fmt.format(i) for i in range(int(config["keys"]))]


def poisson_arrivals(g: np.random.Generator, rate_per_s: float, seconds: float) -> np.ndarray:
    """Open-loop Poisson arrival times in ns since the window opened, on
    the count the rate gives: ``round(rate x seconds)`` times drawn
    uniformly over the window and sorted (a Poisson process conditioned
    on its count), so every seed offers the same number of requests and
    only their order and spacing change."""
    n = int(round(rate_per_s * seconds))
    return np.sort(g.integers(0, int(seconds * 1e9), n, dtype=np.int64))


def key_law_probs(law: dict, n: int) -> np.ndarray:
    """Probability of each key rank under the mix's law."""
    name = law["name"]
    if name == "uniform":
        return np.full(n, 1.0 / n)
    if name == "zipf":
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** float(law["theta"])
        return w / w.sum()
    raise ValueError(f"unknown key law {name!r}")


class KeyLaw:
    """Draws key indices under a mix's law. Ranks map to keys by a
    permutation drawn from the run's seed alone, so the client and every
    peer find the same keys hot, as a cluster's nodes do for one user,
    and which keys are hot changes with the seed while the law does not."""

    def __init__(self, seed: int, law: dict, n_keys: int):
        self.n = n_keys
        self.uniform = law["name"] == "uniform"
        self.cdf = None if self.uniform else np.cumsum(key_law_probs(law, n_keys))
        self.perm = rng(seed, "key-ranks").permutation(n_keys)

    def draw(self, g: np.random.Generator, count: int) -> np.ndarray:
        if self.uniform:
            ranks = g.integers(0, self.n, count)
        else:
            ranks = np.minimum(np.searchsorted(self.cdf, g.random(count), side="right"), self.n - 1)
        return self.perm[ranks].astype(np.int64)


def take_schedule(seed: int, stream: str, traffic: dict, config: dict, seconds: float) -> Dict[str, np.ndarray]:
    """One client's takes: ``due_ns`` and ``key`` arrays, in due order."""
    g = rng(seed, stream)
    due = poisson_arrivals(g, float(traffic["rate_per_s"]), seconds)
    keys = KeyLaw(seed, traffic["key_law"], int(config["keys"])).draw(g, len(due))
    return {"due_ns": due, "key": keys}


def write_schedule(path: str, due_ns: np.ndarray, target: np.ndarray) -> None:
    """The open-loop client's schedule file (see openloop.cpp)."""
    rec = np.zeros(len(due_ns), dtype=[("due", "<i8"), ("target", "<u4"), ("pad", "<u4")])
    rec["due"] = due_ns
    rec["target"] = target
    rec.tofile(path)


RESULT_DTYPE = np.dtype([("sent", "<i8"), ("done", "<i8"), ("status", "<i4"), ("value", "<i4")])


def read_results(path: str) -> np.ndarray:
    return np.fromfile(path, dtype=RESULT_DTYPE)


# ---------------------------------------------------------------------------
# What the peers do: one hook per traffic kind, driven by the peers' thread.


class TakePeers:
    """Peers that take on their own, as the node's cluster mates do.

    Each peer draws its own Poisson takes at the mix's peer rate on the
    mix's key law, admits each by its own sequential token bucket over
    its own view (capacity ``freq``, refilled at ``freq`` per period),
    and books each admitted take on its lane's ``taken`` as whole
    tokens. Peers ship spend only: their lanes' ``added`` stays at the
    preload value, so the node's view never exceeds its capacity, no
    take of the node forfeits, and its own ``taken`` lane is exactly its
    admitted takes.
    """

    on_ack = False

    def __init__(self, seed: int, traffic: dict, config: dict, seconds: float, n_peers: int):
        freq, per_ns = parse_rate(config["rate"])
        self.cap = float(freq)
        self.per_token_ns = per_ns / freq
        peer_rate = float(traffic["rate_per_s"]) * float(traffic.get("peer_rate_factor", 1.0))
        self.schedules = []
        law = KeyLaw(seed, traffic["key_law"], int(config["keys"]))
        for i in range(n_peers):
            g = rng(seed, f"peer{i}")
            due = poisson_arrivals(g, peer_rate, seconds)
            self.schedules.append((due, law.draw(g, len(due))))
        n = int(config["keys"])
        self.tokens = [np.full(n, self.cap) for _ in range(n_peers)]
        self.last = [np.zeros(n, np.int64) for _ in range(n_peers)]
        self.pos = [0] * n_peers
        self.t0_ns = None  # monotonic ns of the window's opening
        self.seconds_ns = int(seconds * 1e9)
        self.attempted = [0] * n_peers
        self.admitted = [0] * n_peers

    def __call__(self, ps, now: int, peers) -> None:
        if self.t0_ns is None or now < self.t0_ns:
            return
        t = min(now - self.t0_ns, self.seconds_ns)
        for p in peers:
            i = ps.peers.index(p)
            due, keys = self.schedules[i]
            lo = self.pos[i]
            hi = int(np.searchsorted(due, t, side="right"))
            if hi <= lo:
                continue
            self.pos[i] = hi
            tok, last = self.tokens[i], self.last[i]
            booked = []
            for d, k in zip(due[lo:hi].tolist(), keys[lo:hi].tolist()):
                have = min(self.cap, tok[k] + (d - last[k]) / self.per_token_ns)
                last[k] = d
                if have >= 1.0:
                    have -= 1.0
                    booked.append(k)
                tok[k] = have
            self.attempted[i] += hi - lo
            self.admitted[i] += len(booked)
            if booked:
                uk, cnt = np.unique(np.array(booked, np.int64), return_counts=True)
                ps.set_lane(p, uk, p.added[uk], p.taken[uk] + cnt * NANO_I)

    def done(self) -> bool:
        return all(self.pos[i] >= len(s[0]) for i, s in enumerate(self.schedules))


NANO_I = 1_000_000_000


def parse_rate(rate: str) -> tuple:
    """``"100:1s"`` → (freq, period in ns). Periods take ns, us, ms, s,
    m or h."""
    freq, per = rate.split(":")
    units = {"ns": 1, "us": 10**3, "ms": 10**6, "s": 10**9, "m": 60 * 10**9, "h": 3600 * 10**9}
    for u in ("ns", "us", "ms", "s", "m", "h"):
        if per.endswith(u) and per[: -len(u)].replace(".", "", 1).isdigit():
            return int(freq), int(float(per[: -len(u)]) * units[u])
    raise ValueError(f"bad rate {rate!r}")
