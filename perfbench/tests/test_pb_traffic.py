"""Schedules and key laws are reproducible from the seed."""

import numpy as np

from perfbench import traffic

CONFIG = {"keys": 10000, "key_format": "user-{:07d}", "rate": "100:1s"}
ZIPF = {"rate_per_s": 2000, "key_law": {"name": "zipf", "theta": 0.99}}
UNIFORM = {"rate_per_s": 2000, "key_law": {"name": "uniform"}}


def test_same_seed_same_schedule():
    big = 2**31 + 12345
    a = traffic.take_schedule(big, "client", ZIPF, CONFIG, 3.0)
    b = traffic.take_schedule(big, "client", ZIPF, CONFIG, 3.0)
    assert np.array_equal(a["due_ns"], b["due_ns"]) and np.array_equal(a["key"], b["key"])
    c = traffic.take_schedule(big + 1, "client", ZIPF, CONFIG, 3.0)
    assert not np.array_equal(a["key"][:100], c["key"][:100])
    d = traffic.take_schedule(big, "peer0", ZIPF, CONFIG, 3.0)
    assert not np.array_equal(a["due_ns"][:100], d["due_ns"][:100])


def test_poisson_rate_and_order():
    s = traffic.take_schedule(7, "client", UNIFORM, CONFIG, 10.0)
    due = s["due_ns"]
    assert np.all(np.diff(due) >= 0) and due[-1] < 10e9
    assert len(due) == 20000  # every seed offers the same count
    gaps = np.diff(due) / 1e9
    assert abs(gaps.mean() - 1 / 2000) < 0.05 / 2000
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05  # exponential gaps


def test_zipf_law():
    g = traffic.rng(5, "x")
    keys = traffic.KeyLaw(5, ZIPF["key_law"], 10000).draw(g, 200000)
    counts = np.sort(np.bincount(keys, minlength=10000))[::-1]
    p = traffic.key_law_probs(ZIPF["key_law"], 10000)
    assert abs(counts[0] / 200000 - p[0]) < 0.01  # the top key draws ~10%
    assert counts[0] / counts[9] > 5
    u = np.bincount(traffic.KeyLaw(5, UNIFORM["key_law"], 10000).draw(g, 200000), minlength=10000)
    assert u.max() < 60


def test_client_and_peers_share_the_hot_keys():
    big = 2**31 + 99
    client = traffic.take_schedule(big, "client", ZIPF, CONFIG, 3.0)["key"]
    peer = traffic.take_schedule(big, "peer0", ZIPF, CONFIG, 3.0)["key"]
    top = lambda k: set(np.argsort(-np.bincount(k, minlength=10000))[:3].tolist())  # noqa: E731
    assert top(client) == top(peer)
    other = traffic.take_schedule(big + 1, "client", ZIPF, CONFIG, 3.0)["key"]
    assert top(other) != top(client)


def test_parse_rate():
    assert traffic.parse_rate("100:1s") == (100, 10**9)
    assert traffic.parse_rate("5:250ms") == (5, 250 * 10**6)
    assert traffic.parse_rate("8:1m") == (8, 60 * 10**9)
