"""What the chip bring-up pinned down, checked on the CPU.

* the refill's integer IEEE emulation against the host's float64, on the
  regimes where XLA's TPU float64 emulation was seen to differ;
* the merge kernel is settled at engine start and never falls back in
  silence;
* the compile cache lives where one helper says;
* the native library is named after its sources;
* ``bench.py`` knows the peak of every device it may measure.
"""

from __future__ import annotations

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import patrol_tpu  # noqa: F401  (x64)
from patrol_tpu.models.limiter import NANO, LimiterConfig
from patrol_tpu.ops.take import host_refill_grant_nt, refill_grant_nt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _grid(regime: str, n: int, rng) -> tuple:
    if regime == "wide":  # every magnitude of delta and interval
        d = rng.integers(0, 2**63 - 1, n, dtype=np.int64) >> rng.integers(0, 64, n)
        iv = rng.integers(1, 2**63 - 1, n, dtype=np.int64) >> rng.integers(0, 63, n)
    elif regime == "near_2_53":
        d = 2**53 + rng.integers(-(10**6), 10**6, n)
        iv = rng.integers(1, 2**40, n)
    elif regime == "multiples":  # exact quotients and their neighbours
        iv = rng.integers(1, 2**43, n)
        d = iv * rng.integers(0, 10**6, n) + rng.integers(-2, 3, n)
    else:  # "small": short intervals, small deltas
        d = rng.integers(0, 2**20, n)
        iv = rng.integers(1, 2**12, n)
    return np.maximum(d, 0).astype(np.int64), np.maximum(iv, 1).astype(np.int64)


@pytest.mark.parametrize("seed,regime", enumerate(["wide", "near_2_53", "multiples", "small"]))
def test_refill_bit_identical_to_float64(seed, regime):
    d, iv = _grid(regime, 20_000, np.random.default_rng(seed))
    got = np.asarray(jax.jit(refill_grant_nt)(jnp.asarray(d), jnp.asarray(iv)))
    want = np.floor(
        np.clip((d.astype(np.float64) / iv.astype(np.float64)) * 1e9, 0.0, 2.0**62)
    ).astype(np.int64)
    bad = np.flatnonzero(got != want)
    assert bad.size == 0, [(int(d[i]), int(iv[i]), int(got[i]), int(want[i])) for i in bad[:5]]


def test_refill_edges_and_host_twin():
    edges = [0, 1, 2, 3, 2**52 - 1, 2**52, 2**52 + 1, 2**53 - 1, 2**53, 2**53 + 1,
             2**53 + 3, 2**62, 2**63 - 1, NANO - 1, NANO, 10**18]
    pairs = np.array([(a, b) for a in edges for b in edges if b >= 1], np.int64)
    got = np.asarray(jax.jit(refill_grant_nt)(jnp.asarray(pairs[:, 0]), jnp.asarray(pairs[:, 1])))
    # freq=1 makes interval == per_ns, so the host twin sees the same pair.
    want = [host_refill_grant_nt(int(a), 1, int(b)) for a, b in pairs]
    assert got.tolist() == want


def test_host_twin_degenerate_rates():
    assert host_refill_grant_nt(10**12, 0, 10**9) == 0  # freq 0
    assert host_refill_grant_nt(10**12, 5, 0) == 0  # per 0
    assert host_refill_grant_nt(10**12, 10, 3) == 0  # per < freq: interval 0
    assert host_refill_grant_nt(2**63 - 1, 1, 1) == 2**62  # clipped


def test_compile_cache_dir(monkeypatch):
    from patrol_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.cache_dir() == "/elsewhere"


def test_native_library_named_after_its_sources():
    from patrol_tpu import native

    if os.environ.get("PATROL_NATIVE_LIB"):
        pytest.skip("an explicit library overrides the content name")
    name = os.path.basename(native._LIB)
    assert name.startswith("libpatrolhost-") and name.endswith(".so")
    assert native._content_lib() == native._LIB


def test_bench_peak_table(monkeypatch):
    sys.path.insert(0, REPO)
    try:
        bench = importlib.import_module("bench")
    finally:
        sys.path.remove(REPO)
    assert bench._hbm_peak_gbps() == 0.0  # the CPU: a smoke, no roofline

    class Dev:
        platform = "tpu"
        device_kind = "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(RuntimeError, match="no HBM peak"):
        bench._hbm_peak_gbps()
    Dev.device_kind = "TPU v5 lite"
    assert bench._hbm_peak_gbps() == 819.0


@pytest.mark.parametrize("replicas", [1, 2])
def test_mesh_state_is_made_sharded(replicas):
    """Every device holds its share from the start; nothing is built on
    the first device and moved (4M x 256 would not fit one v5e)."""
    from patrol_tpu.runtime.mesh_engine import MeshEngine

    cfg = LimiterConfig(buckets=1024, nodes=4)
    eng = MeshEngine(cfg, replicas=replicas)
    try:
        held = eng.stats()["mesh_shard_bytes"]
        shards = eng.plan.shards
        assert len(held) == len(jax.devices())
        assert set(held.values()) == {cfg.buckets // shards * (2 * cfg.nodes + 1) * 8}
    finally:
        eng.stop()


def test_smoke_load_has_positive_lanes():
    """chip_smoke's replication load: the per-lane max join of every
    bucket is its reference balance, and lanes with positive balances
    make some partial joins read at or above capacity (the case idle GC
    once reclaimed, dropping acknowledged remote lanes)."""
    import random

    sys.path.insert(0, REPO)
    try:
        smoke = importlib.import_module("chip_smoke")
    finally:
        sys.path.remove(REPO)
    entries, ref = smoke.make_load(random.Random(5), 400, list(range(1, 32)))
    lanes = {}
    for e in entries:
        a, t = lanes.get((e.name, e.slot), (0, 0))
        lanes[e.name, e.slot] = (max(a, e.added_nt), max(t, e.taken_nt))
    joined = {name: freq * NANO for name, (freq, _) in ref.items()}
    full_partial = 0
    for (name, _slot), (a, t) in lanes.items():
        joined[name] += a - t
        full_partial += a > t
    assert all(joined[n] == b * NANO for n, (_f, b) in ref.items())
    assert all(0 <= b < f for f, b in ref.values())
    assert full_partial > 0
