"""On-chip probe: block-sparse Pallas merge vs XLA scatter.

Compares the vector-RMW pallas kernel against the pair-window XLA scatter
on (a) a zipf-like concentrated batch (hot working set -> few touched
512-row blocks: the realistic rate-limiter traffic, BASELINE config #2)
and (b) a uniform batch over 1M rows (every block touched: the
adversarial case where block streaming degenerates to a dense sweep).

Usage: python scripts/probe_pallas.py [K] [hot_buckets]
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from patrol_tpu.models.limiter import LimiterState  # noqa: E402
from patrol_tpu.ops import pallas_merge  # noqa: E402
from patrol_tpu.ops.merge import MergeBatch, merge_batch  # noqa: E402

B = int(1e6)
N = 256
K = int(sys.argv[1]) if len(sys.argv) > 1 else 65536
HOT = int(sys.argv[2]) if len(sys.argv) > 2 else 10_000


def _force(state):
    s = jnp.sum(state.pn).astype(jnp.int64) + jnp.sum(state.elapsed)
    return int(jax.device_get(s))


def mk_state():
    return LimiterState(
        pn=jnp.zeros((B, N, 2), jnp.int64), elapsed=jnp.zeros((B,), jnp.int64)
    )


def time_fn(run, state, n_lo=2, n_hi=8, repeats=3):
    state = run(state, 0)
    _force(state)
    best = {n_lo: float("inf"), n_hi: float("inf")}
    for _ in range(repeats):
        for n in (n_lo, n_hi):
            t0 = time.perf_counter()
            for i in range(n):
                state = run(state, i)
            _force(state)
            best[n] = min(best[n], time.perf_counter() - t0)
    return max(best[n_hi] - best[n_lo], 1e-9) / (n_hi - n_lo)


def main():
    rng = np.random.default_rng(11)
    refused = pallas_merge.compile_error(mk_state())
    print(f"K={K} hot={HOT} pallas_refused={refused}")
    for label, rows_np in (
        ("zipf-hot", rng.integers(0, HOT, K).astype(np.int64)),
        ("uniform", rng.integers(0, B, K).astype(np.int64)),
    ):
        slots_np = rng.integers(0, N, K).astype(np.int64)
        a_np = rng.integers(1, 1 << 40, K).astype(np.int64)
        t_np = rng.integers(1, 1 << 40, K).astype(np.int64)
        e_np = rng.integers(1, 1 << 40, K).astype(np.int64)
        touched = len(np.unique(rows_np // pallas_merge.ROWS_PER_BLOCK))

        # XLA scatter path (device arrays prebuilt, donated chain)
        mb = MergeBatch(
            rows=jnp.asarray(rows_np, jnp.int32),
            slots=jnp.asarray(slots_np, jnp.int32),
            added_nt=jnp.asarray(a_np),
            taken_nt=jnp.asarray(t_np),
            elapsed_ns=jnp.asarray(e_np),
        )

        @partial(jax.jit, donate_argnums=0)
        def sc_step(s, i, mb=mb):
            return merge_batch(
                s,
                mb._replace(
                    added_nt=mb.added_nt + i,
                    taken_nt=mb.taken_nt + i,
                    elapsed_ns=mb.elapsed_ns + i,
                ),
            )

        per = time_fn(lambda s, i: sc_step(s, jnp.int64(i)), mk_state())
        print(
            f"{label:9s} xla-scatter {per * 1e3:9.3f} ms "
            f"{K / per / 1e6:8.2f} M-deltas/s (blocks {touched})"
        )

        if refused is None:
            # pallas path: host prep (sort+plan) is part of the cost in
            # production; measure device time with prep hoisted (prep is
            # ~1 ms numpy at K=65536, reported separately).
            t0 = time.perf_counter()
            order, block_ids, starts, ends, _ = pallas_merge.prepare(rows_np, B)
            prep_ms = (time.perf_counter() - t0) * 1e3

            def split_host(v):
                v = np.ascontiguousarray(v[order])
                return jnp.asarray(v.view(np.int32).reshape(len(v), 2))

            dargs = (
                jnp.asarray(block_ids),
                jnp.asarray(starts),
                jnp.asarray(ends),
                jnp.asarray(rows_np[order].astype(np.int32)),
                jnp.asarray(slots_np[order].astype(np.int32)),
                split_host(a_np),
                split_host(t_np),
                split_host(e_np),
            )

            def pal_step(s, i):
                # i is ignored: values identical each iter, but pallas_call
                # is opaque to the algebraic simplifier so the chain can't
                # collapse (verified: timing scales with n).
                return pallas_merge._merge_pallas_device(s, *dargs)

            per = time_fn(pal_step, mk_state())
            print(
                f"{label:9s} pallas      {per * 1e3:9.3f} ms "
                f"{K / per / 1e6:8.2f} M-deltas/s (prep {prep_ms:.1f} ms)"
            )


if __name__ == "__main__":
    main()
