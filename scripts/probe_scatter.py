"""On-chip probe: which scatter shape does TPU XLA actually vectorize?

The r3 honest capture put the element-granular scatter-merge at ~2.5M
deltas/s (~133 ns per element update, serialized). This probe measures the
alternatives before committing to a kernel redesign:

  elem3    - current merge_batch: 3 element scatters (added, taken, elapsed)
  pair     - lane-pair window: pn.at[rows, slots].max(pair[K,2]) + elapsed elem
  row      - row window: pn.at[rows].max(onehot[K,N,2]) + elapsed elem
  row_only - the row-window pn scatter alone
  el_only  - the elapsed element scatter alone
  row_flags- row_only with indices_are_sorted (rows pre-sorted host-side)
  el_flags - el_only with indices_are_sorted
  take     - current take_batch commit (2 elem adds + 1 elapsed add)
  take_row - row-window commit: pn.at[rows].add(onehot) + elapsed add

Methodology is bench.py's: unrolled chain inside one jit on a donated
input (a per-execute transport cost of ~60-80 ms was measured in r3),
values varied with the
unroll index so CSE can't collapse the chain, forced completion via a
dependent checksum readback, differential (hi-lo)/(n_hi-n_lo) windows.

Usage: python scripts/probe_scatter.py [stage ...]
"""
from __future__ import annotations

import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

B = int(1e6)
N = 256
K = 65536

_PROBE = {}


def _force(tree):
    leaves = tuple(jax.tree_util.tree_leaves(tree))
    key = tuple((l.shape, str(l.dtype)) for l in leaves)
    p = _PROBE.get(key)
    if p is None:
        def _sum(ls):
            tot = jnp.zeros((), jnp.int64)
            for l in ls:
                tot = tot + jnp.sum(l).astype(jnp.int64)
            return tot
        p = jax.jit(_sum)
        _PROBE[key] = p
    return int(jax.device_get(p(leaves)))


def bench(fn, mk_state, *args, n_lo=2, n_hi=8, repeats=3):
    def make_run(n):
        @partial(jax.jit, donate_argnums=0)
        def run(s, *a):
            for i in range(n):
                s = fn(s, *a, i)
            return s
        return run

    run_lo, run_hi = make_run(n_lo), make_run(n_hi)
    state = mk_state()
    state = run_lo(state, *args)
    state = run_hi(state, *args)
    _force(state)
    best_lo = best_hi = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        state = run_lo(state, *args)
        _force(state)
        best_lo = min(best_lo, time.perf_counter() - t0)
        t0 = time.perf_counter()
        state = run_hi(state, *args)
        _force(state)
        best_hi = min(best_hi, time.perf_counter() - t0)
    del state
    return max(best_hi - best_lo, 1e-9) / (n_hi - n_lo)


def main():
    want = set(sys.argv[1:]) or None
    rng = np.random.default_rng(7)
    rows_np = rng.integers(0, B, K).astype(np.int32)
    rows_sorted_np = np.sort(rows_np)
    slots_np = rng.integers(0, N, K).astype(np.int32)
    rows = jnp.asarray(rows_np)
    rows_sorted = jnp.asarray(rows_sorted_np)
    slots = jnp.asarray(slots_np)
    a = jnp.asarray(rng.integers(1, 1 << 40, K).astype(np.int64))
    t = jnp.asarray(rng.integers(1, 1 << 40, K).astype(np.int64))
    e = jnp.asarray(rng.integers(1, 1 << 40, K).astype(np.int64))

    def mk_pn_el():
        return (
            jnp.zeros((B, N, 2), jnp.int64),
            jnp.zeros((B,), jnp.int64),
        )

    def mk_pn():
        return jnp.zeros((B, N, 2), jnp.int64)

    def mk_el():
        return jnp.zeros((B,), jnp.int64)

    oh = jax.jit(
        lambda slots_, a_, t_: jnp.where(
            (jnp.arange(N)[None, :, None] == slots_[:, None, None]),
            jnp.stack([a_, t_], -1)[:, None, :],
            jnp.int64(0),
        )
    )

    stages = {}

    def elem3(s, i):
        pn, el = s
        pn = pn.at[rows, slots, 0].max(a + i)
        pn = pn.at[rows, slots, 1].max(t + i)
        el = el.at[rows].max(e + i)
        return (pn, el)

    stages["elem3"] = (elem3, mk_pn_el, ())

    def pair(s, i):
        pn, el = s
        pn = pn.at[rows, slots].max(jnp.stack([a + i, t + i], -1))
        el = el.at[rows].max(e + i)
        return (pn, el)

    stages["pair"] = (pair, mk_pn_el, ())

    def row(s, i):
        pn, el = s
        pn = pn.at[rows].max(oh(slots, a + i, t + i))
        el = el.at[rows].max(e + i)
        return (pn, el)

    stages["row"] = (row, mk_pn_el, ())

    def row_only(pn, i):
        return pn.at[rows].max(oh(slots, a + i, t + i))

    stages["row_only"] = (row_only, mk_pn, ())

    def el_only(el, i):
        return el.at[rows].max(e + i)

    stages["el_only"] = (el_only, mk_el, ())

    def row_flags(pn, i):
        return pn.at[rows_sorted].max(
            oh(slots, a + i, t + i), indices_are_sorted=True
        )

    stages["row_flags"] = (row_flags, mk_pn, ())

    def el_flags(el, i):
        return el.at[rows_sorted].max(e + i, indices_are_sorted=True)

    stages["el_flags"] = (el_flags, mk_el, ())

    # --- the engine's REAL uniform tick kernel (r5): host C++ fold →
    # sorted unique sentinel-padded pairs → flagged scatter. The plain
    # elem3 above is the unfolded class the r4 bench measured; if the
    # flags buy a material win, the bench's scatter stage should measure
    # THIS (it is what the engine tick dispatches for uniform batches).
    from patrol_tpu.ops.merge import FoldedMergeBatch, merge_batch_folded
    from patrol_tpu.runtime.engine import DeltaArrays, DeviceEngine

    deltas_np = DeltaArrays(
        rows=rows_np.astype(np.int64), slots=slots_np.astype(np.int64),
        added_nt=np.asarray(a), taken_nt=np.asarray(t),
        elapsed_ns=np.asarray(e), scalar=np.zeros(K, bool),
    )
    packed_np = DeviceEngine._fold_lane_merges(deltas_np)
    packed = jnp.asarray(packed_np)

    def folded(s, i):
        from patrol_tpu.models.limiter import LimiterState

        st = LimiterState(pn=s[0], elapsed=s[1])
        st = merge_batch_folded(
            st,
            FoldedMergeBatch(
                rows=packed[0].astype(jnp.int32),
                slots=packed[1].astype(jnp.int32),
                added_nt=packed[2] + i,
                taken_nt=packed[3] + i,
                erows=packed[4].astype(jnp.int32),
                elapsed_ns=packed[5] + i,
            ),
        )
        return (st.pn, st.elapsed)

    stages["folded"] = (folded, mk_pn_el, ())

    # --- folded + flat key: the folded pack's sorted UNIQUE (row,slot)
    # pairs re-keyed as row*N+slot — one index dim, sorted+unique flags,
    # sentinel tail dropped via OOB mode="drop" (sentinel rows are far
    # above B so their flat keys are OOB of B*N).
    Kp = packed_np.shape[1]
    flat_packed = jnp.asarray(packed_np[0] * N + packed_np[1])
    p2 = jnp.asarray(packed_np[2])
    p3 = jnp.asarray(packed_np[3])
    p4 = jnp.asarray(packed_np[4].astype(np.int32))
    p5 = jnp.asarray(packed_np[5])

    def folded_flat(s, i):
        pn, el = s
        fp = pn.reshape(B * N, 2)
        fp = fp.at[flat_packed].max(
            jnp.stack([p2 + i, p3 + i], -1),
            indices_are_sorted=True, unique_indices=True, mode="drop",
        )
        el = el.at[p4].max(
            p5 + i, indices_are_sorted=True, unique_indices=True,
            mode="drop",
        )
        return (fp.reshape(B, N, 2), el)

    stages["folded_flat"] = (folded_flat, mk_pn_el, ())

    # --- flat-key single scatter: same [B,N,2] memory viewed [B*N, 2],
    # ONE pair-window scatter at row*N+slot (one index dim instead of
    # two). A probe-only layout question: reshape is free, so a win here
    # is adoptable without moving bytes.
    flat_idx = jnp.asarray(rows_np.astype(np.int64) * N + slots_np)
    flat_sorted = jnp.asarray(
        np.sort(rows_np.astype(np.int64) * N + slots_np)
    )

    def flat(s, i):
        pn, el = s
        fp = pn.reshape(B * N, 2)
        fp = fp.at[flat_idx].max(jnp.stack([a + i, t + i], -1))
        el = el.at[rows].max(e + i)
        return (fp.reshape(B, N, 2), el)

    stages["flat"] = (flat, mk_pn_el, ())

    def flat_flags(s, i):
        pn, el = s
        fp = pn.reshape(B * N, 2)
        fp = fp.at[flat_sorted].max(
            jnp.stack([a + i, t + i], -1), indices_are_sorted=True
        )
        el = el.at[rows_sorted].max(e + i, indices_are_sorted=True)
        return (fp.reshape(B, N, 2), el)

    stages["flat_flags"] = (flat_flags, mk_pn_el, ())

    # --- take-shaped commits (K unique rows, add semantics) ---
    KT = 4096
    trows = jnp.asarray(
        rng.choice(B, KT, replace=False).astype(np.int32)
    )
    tslots = jnp.asarray(rng.integers(0, N, KT).astype(np.int32))
    da = jnp.asarray(rng.integers(1, 1 << 30, KT).astype(np.int64))
    dt = jnp.asarray(rng.integers(1, 1 << 30, KT).astype(np.int64))
    de = jnp.asarray(rng.integers(1, 1 << 30, KT).astype(np.int64))
    oh_t = jax.jit(
        lambda a_, t_: jnp.where(
            (jnp.arange(N)[None, :, None] == tslots[:, None, None]),
            jnp.stack([a_, t_], -1)[:, None, :],
            jnp.int64(0),
        )
    )

    def take_elem(s, i):
        pn, el = s
        pn = pn.at[trows, tslots, 0].add(da + i)
        pn = pn.at[trows, tslots, 1].add(dt + i)
        el = el.at[trows].add(de + i)
        return (pn, el)

    stages["take"] = (take_elem, mk_pn_el, ())

    def take_row(s, i):
        pn, el = s
        pn = pn.at[trows].add(oh_t(da + i, dt + i))
        el = el.at[trows].add(de + i)
        return (pn, el)

    stages["take_row"] = (take_row, mk_pn_el, ())

    def take_gather(s, i):
        # the full take kernel's memory shape: gather + compute + commit
        pn, el = s
        rows_g = pn[trows]
        sums = rows_g[:, :, 0].sum(-1) - rows_g[:, :, 1].sum(-1)
        pn = pn.at[trows].add(oh_t(da + i + sums * 0, dt + i))
        el = el.at[trows].add(de + i)
        return (pn, el)

    stages["take_gather"] = (take_gather, mk_pn_el, ())

    for name, (fn, mk, args) in stages.items():
        if want and name not in want:
            continue
        try:
            per = bench(fn, mk, *args)
        except Exception as ex:  # noqa: BLE001
            print(f"{name:12s} FAILED: {ex}")
            continue
        kk = KT if name.startswith("take") else K
        print(
            f"{name:12s} {per * 1e3:9.3f} ms/step  "
            f"{kk / per / 1e6:8.2f} M-deltas/s"
        )


if __name__ == "__main__":
    main()
