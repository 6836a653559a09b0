"""Probe: dense CvRDT join formulations vs jax's s64 split-pair emulation.

VERDICT r3 item 2: the canonical dense sweep lands at 48.3M merges/s
(594.7 GB/s implied of 819) — the gap to the bandwidth bound is the s64
max emulation on a chip without native int64. All CRDT planes are
NON-NEGATIVE (lanes are monotone grow-only), so s64 max is order-preserving
on the value's (hi, lo) u32 pair — candidate reformulations:

  s64      jnp.maximum on int64 (current merge_dense)
  u64      bitcast to uint64, maximum, bitcast back (drops sign handling)
  lex32    bitcast to u32[..,2]; lexicographic (hi, lo) compare; ONE
           interleaved pair select (jnp.where on the [..,2] view)
  lex32x   same compare, arithmetic mask select (xor/and instead of where)

Timing: the proven device_loop differential from bench.py (fori carry
prevents CSE of idempotent joins; forced completion via dependent checksum
readback; min-per-window then difference). Correctness: each candidate is
checksum-compared against s64 on the same inputs before timing.

Run on the chip:  chiprun -- python scripts/probe_dense_u32.py
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

import patrol_tpu  # noqa: F401  (x64)

B = int(os.environ.get("PROBE_B", 500_000))
N = int(os.environ.get("PROBE_N", 256))


# Every candidate takes (state, other, i) and joins state with (other + i):
# the +i (an s64 add, identical cost in all candidates) makes each loop
# iteration VALUE-DISTINCT — a plain idempotent max chain reaches its
# fixpoint after one step, and both the compiler and the execution
# layer can then collapse the remaining iterations (the r4 first
# probe "measured" 73 PB/s of HBM traffic that way).


def max_s64(a, b, i):
    return jnp.maximum(a, b + i)


def max_u64(a, b, i):
    return lax.bitcast_convert_type(
        jnp.maximum(
            lax.bitcast_convert_type(a, jnp.uint64),
            lax.bitcast_convert_type(b + i, jnp.uint64),
        ),
        jnp.int64,
    )


def _lex_gt(a2, b2):
    a_lo, a_hi = a2[..., 0], a2[..., 1]
    b_lo, b_hi = b2[..., 0], b2[..., 1]
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def max_lex32(a, b, i):
    a2 = lax.bitcast_convert_type(a, jnp.uint32)
    b2 = lax.bitcast_convert_type(b + i, jnp.uint32)
    out = jnp.where(_lex_gt(a2, b2)[..., None], a2, b2)
    return lax.bitcast_convert_type(out, jnp.int64)


def max_lex32x(a, b, i):
    a2 = lax.bitcast_convert_type(a, jnp.uint32)
    b2 = lax.bitcast_convert_type(b + i, jnp.uint32)
    mask = (
        _lex_gt(a2, b2)[..., None]
        .astype(jnp.uint32)
        * jnp.uint32(0xFFFFFFFF)
    )
    out = b2 ^ ((a2 ^ b2) & mask)
    return lax.bitcast_convert_type(out, jnp.int64)


CANDIDATES = {
    "s64": max_s64,
    "u64": max_u64,
    "lex32": max_lex32,
    "lex32x": max_lex32x,
}


def mk(B, N):
    @jax.jit
    def _mk():
        row = jnp.arange(B, dtype=jnp.int64)[:, None, None]
        lane = jnp.arange(N, dtype=jnp.int64)[None, :, None]
        side = jnp.arange(2, dtype=jnp.int64)[None, None, :]
        a = (row * 7 + lane * 13 + side * 3) % (10**10)
        b = (row * 11 + lane * 5 + side * 17) % (10**10)
        # Spice the high words so the hi/lo split actually matters.
        a = a + (row % 5) * (1 << 33)
        b = b + (row % 3) * (1 << 33)
        return a, b

    return _mk()


@jax.jit
def _checksum_probe(v):
    return jnp.sum(v)


def checksum(x):
    return int(jax.device_get(_checksum_probe(x)))


def bench_one(fn, a, b, iters_lo=2, iters_hi=14, repeats=3):
    @partial(jax.jit, donate_argnums=0)
    def loop_n(s, n, o):
        return lax.fori_loop(
            0, n, lambda i, st: fn(st, o, i.astype(jnp.int64)), s
        )

    s = a + 0  # private carry copy
    for _ in range(2):
        s = loop_n(s, jnp.int32(iters_lo), b)
    s = loop_n(s, jnp.int32(iters_hi), b)
    checksum(s)
    best_lo = best_hi = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        s = loop_n(s, jnp.int32(iters_lo), b)
        checksum(s)
        best_lo = min(best_lo, time.perf_counter() - t0)
        t0 = time.perf_counter()
        s = loop_n(s, jnp.int32(iters_hi), b)
        checksum(s)
        best_hi = min(best_hi, time.perf_counter() - t0)
    return max(best_hi - best_lo, 1e-9) / (iters_hi - iters_lo)


def main():
    print(f"platform={jax.default_backend()} devices={jax.devices()}", flush=True)
    a, b = mk(B, N)
    jax.block_until_ready(a)
    print(f"state {B}x{N}x2 int64 ({B * N * 2 * 8 / 1e9:.2f} GB/plane)", flush=True)

    # Correctness first: all candidates must join to the s64 answer.
    i_test = jnp.int64(3)
    want = checksum(jnp.maximum(a, b + i_test))
    bad = []
    for name, fn in CANDIDATES.items():
        got = checksum(jax.jit(fn)(a, b, i_test))
        status = "ok" if got == want else f"MISMATCH want={want} got={got}"
        print(f"correctness {name}: {status}", flush=True)
        if got != want:
            bad.append(name)
    for name in bad:
        CANDIDATES.pop(name)

    bytes_per = 3 * B * N * 2 * 8
    for name, fn in CANDIDATES.items():
        dt = bench_one(fn, a, b)
        print(
            f"{name}: {dt * 1e3:.3f} ms/sweep  "
            f"{B / dt / 1e6:.1f}M merges/s  "
            f"{bytes_per / dt / 1e9:.1f} GB/s implied",
            flush=True,
        )


if __name__ == "__main__":
    main()
