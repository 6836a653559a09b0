"""Pallas TPU kernel for the scatter-merge hot path.

XLA lowers ``state.at[rows, slots].max(values)`` (ops/merge.py) to a scatter
that serializes on TPU. This kernel restructures the op around the memory
system instead:

1. The host sorts the delta batch by bucket row (cheap numpy argsort) and
   computes which 512-row *blocks* of the state are touched.
2. The grid iterates only the touched blocks — block indices arrive via
   scalar prefetch (``PrefetchScalarGridSpec``), so the BlockSpec index_map
   DMAs exactly the needed 512×N×2 state tiles into VMEM and nothing else.
   A merge of K deltas therefore streams O(touched blocks) of state, not
   O(B) and not K serialized HBM round-trips.
3. Inside a block, a scalar loop applies that block's slice of the sorted
   deltas as VMEM read-modify-writes.

Because TPU vector lanes are 32-bit, the int64 CRDT planes are bitcast to
int32 (lo, hi) pairs and merged with a lexicographic max — exact for the
non-negative int64 domain the state invariants guarantee (lanes are
G-counters; ingest clamps negatives, ops/merge.py).

Safety notes baked into the host-side preparation (:func:`prepare`):
* touched-block ids are deduplicated — revisiting a block within one grid
  would race the pipeline's write-back (read-before-write hazard);
* padding of the block-id list uses *untouched* block ids for the same
  reason (processing an untouched block is a no-op copy);
* when every block is touched, a dense ``merge_dense`` sweep is cheaper —
  the engine picks per batch.

Verified against the XLA scatter path in interpret mode (tests) and usable
on CPU the same way; selected on TPU via PATROL_MERGE_KERNEL=auto|pallas
— behind a compile of the engine's real state shape
(:func:`compile_error`). On the installed jax 0.9.0 Mosaic refuses it for
v5e ("cannot statically prove that index in dimension 0 is a multiple of
1024"), so ``pallas`` fails at engine start and ``auto`` stays on the XLA
scatter, which r3 measured honestly at ~130-215 ns per scatter *update*
regardless of window size (scripts/probe_scatter.py). The full r3 kernel
exploration, so the next Mosaic bump can be retried with data:

* This kernel's per-delta VMEM read-modify-writes lower only as vector
  dynamic slices, and Mosaic requires a dynamic dim-0 slice index it can
  statically prove tile-aligned ("cannot statically prove that index in
  dimension 0 is a multiple of 128") — arbitrary per-row RMW inside one
  VMEM block is not expressible today.
* A DMA-based variant (state in HBM via ``memory_space=ANY``, per-row
  ``make_async_copy`` RMW, D=8 double-buffered pipeline) DOES compile and
  run (scripts/probe_dma_scatter.py): raw row traffic streams at ~3 ns/row
  pipelined. But the CRDT join itself — a lexicographic (hi, lo) int64 max
  on (lo, hi)-interleaved int32 lanes — costs ~190-260 ns/delta in-kernel
  (lane rolls or masked reductions), landing the total at or above the
  XLA scatter's per-update cost. The kernel only wins if state moves to a
  de-interleaved (split lo/hi plane) layout, which would put the whole
  int64 emulation burden on every other op; measured and declined.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from patrol_tpu.models.limiter import LimiterState

ROWS_PER_BLOCK = 512


def _split64(v: jax.Array) -> jax.Array:
    """int64[...] → int32[..., 2] as (lo, hi) words (XLA bitcast order:
    index 0 = least-significant 32 bits)."""
    return jax.lax.bitcast_convert_type(v, jnp.int32)


def _join64(v32: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(v32, jnp.int64)


def _pair_max(a_lo, a_hi, b_lo, b_hi):
    """Lexicographic (hi, lo-unsigned) max — int64 max for non-negative
    values split into 32-bit words."""
    sign = jnp.int32(-0x80000000)
    a_gt = (a_hi > b_hi) | ((a_hi == b_hi) & ((a_lo ^ sign) > (b_lo ^ sign)))
    return jnp.where(a_gt, a_lo, b_lo), jnp.where(a_gt, a_hi, b_hi)


def _kernel(
    block_ids_ref,  # int32[G]       (scalar prefetch)
    starts_ref,  # int32[G]          (scalar prefetch)
    ends_ref,  # int32[G]            (scalar prefetch)
    rows_ref,  # int32[K]            sorted, global row ids
    slots_ref,  # int32[K]
    added_ref,  # int32[K, 2]
    taken_ref,  # int32[K, 2]
    elapsed_ref,  # int32[K, 2]
    pn_in_ref,  # int32[R, N, 2, 2]  (aliased with pn_out)
    el_in_ref,  # int32[R, 2]        (aliased with el_out)
    pn_out_ref,
    el_out_ref,
):
    """Per-block body, VECTOR read-modify-writes only.

    The r2 kernel did per-delta scalar VMEM stores and Mosaic (v5e)
    rejects those ("Cannot store scalars to VMEM"). This version touches
    VMEM exclusively through shapes Mosaic vectorizes:

    * pn: per delta, one dynamic-slice row load [1, N, 2, 2], a one-hot
      lane/plane join built from broadcast scalars, one dynamic-slice row
      store. Non-target lanes join with (0, 0), a no-op under max on the
      non-negative domain.
    * elapsed: per delta, a full-tile [R, 2] one-hot max — no dynamic
      store at all.

    Consecutive deltas hitting the same row are safe: fori_loop is
    sequential, each iteration reads the previous one's store.

    Lowering-hazard rules obeyed throughout (each bisected to a concrete
    failure on jax 0.9.0 / v5e Mosaic, scripts/probe_pallas.py notes):

    * no ``jnp.where`` whose condition compares an iota against a TRACED
      scalar — select lowering recurses in ``_convert_helper``; use the
      ``(cmp).astype(int32) * value`` mask-multiply form instead;
    * no ``//`` or ``%`` on traced scalars (same recursion) — shift/mask;
    * no bare python literals where promotion would insert a scalar
      convert (same recursion) — spell ``jnp.int32(0)``;
    * int32 ``fori_loop`` bounds, or the induction variable arrives as
      int64 under x64 and every mixed index add fails MLIR verification
      ("'arith.addi' op requires the same type for all operands").
    """
    g = pl.program_id(0)
    base = block_ids_ref[g] * ROWS_PER_BLOCK
    n = pn_out_ref.shape[1]

    pn_out_ref[...] = pn_in_ref[...]
    el_out_ref[...] = el_in_ref[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n, 2), 1)
    plane_is_added = (
        jax.lax.broadcasted_iota(jnp.int32, (1, n, 2), 2) == 0
    ).astype(jnp.int32)
    plane_is_taken = jnp.int32(1) - plane_is_added
    rowvec = jax.lax.broadcasted_iota(jnp.int32, (ROWS_PER_BLOCK, 1), 0)

    def body(j, _):
        r = rows_ref[j] - base
        s = slots_ref[j]

        cur = pn_out_ref[pl.dslice(r, 1)]  # [1, N, 2, 2]
        # Mask-multiply select (see hazard rules above): the target lane
        # carries (added, taken) pairs, every other lane carries (0, 0) —
        # the identity of max on the non-negative CRDT domain.
        onehot = (lane == s).astype(jnp.int32)
        val_lo = plane_is_added * added_ref[j, 0] + plane_is_taken * taken_ref[j, 0]
        val_hi = plane_is_added * added_ref[j, 1] + plane_is_taken * taken_ref[j, 1]
        upd_lo = onehot * val_lo
        upd_hi = onehot * val_hi
        new_lo, new_hi = _pair_max(upd_lo, upd_hi, cur[..., 0], cur[..., 1])
        pn_out_ref[pl.dslice(r, 1)] = jnp.stack([new_lo, new_hi], axis=-1)

        el = el_out_ref[...]  # [R, 2]
        hit = (rowvec == r).astype(jnp.int32)
        eu_lo = hit * elapsed_ref[j, 0]
        eu_hi = hit * elapsed_ref[j, 1]
        ne_lo, ne_hi = _pair_max(eu_lo[:, 0], eu_hi[:, 0], el[:, 0], el[:, 1])
        el_out_ref[...] = jnp.stack([ne_lo, ne_hi], axis=-1)
        return 0

    jax.lax.fori_loop(
        starts_ref[g].astype(jnp.int32), ends_ref[g].astype(jnp.int32), body, 0
    )


from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def prepare(
    rows: np.ndarray, num_buckets: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side sort + block planning.

    → (order, block_ids[G], starts[G], ends[G], n_touched). ``order``
    sorts the batch by row; ``block_ids`` are the touched 512-row blocks,
    padded with *untouched* ids up to a power-of-two length (≤ total
    blocks); ``starts[g]:ends[g]`` is block g's slice of the sorted batch
    (empty for padding blocks).
    """
    order = np.argsort(rows, kind="stable")
    sorted_rows = rows[order]
    touched = np.unique(sorted_rows // ROWS_PER_BLOCK).astype(np.int32)
    total_blocks = (num_buckets + ROWS_PER_BLOCK - 1) // ROWS_PER_BLOCK

    g = max(1, len(touched))
    G = 1
    while G < g:
        G <<= 1
    G = min(G, total_blocks)
    if G < len(touched):
        raise ValueError("more touched blocks than padded grid")  # pragma: no cover

    block_ids = np.zeros(G, np.int32)
    block_ids[: len(touched)] = touched
    if len(touched) < G:
        touched_set = set(touched.tolist())
        fill = [b for b in range(total_blocks) if b not in touched_set]
        block_ids[len(touched) :] = np.array(fill[: G - len(touched)], np.int32)

    starts = np.searchsorted(sorted_rows, block_ids * ROWS_PER_BLOCK).astype(np.int32)
    ends = np.searchsorted(sorted_rows, (block_ids + 1) * ROWS_PER_BLOCK).astype(np.int32)
    # Padding blocks have start == end (their searchsorted range is empty).
    return order, block_ids, starts, ends, len(touched)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=0)
def _merge_pallas_device(
    state: LimiterState,
    block_ids,
    starts,
    ends,
    rows,
    slots,
    added,
    taken,
    elapsed,
    interpret: bool = False,
) -> LimiterState:
    B, N = state.pn.shape[0], state.pn.shape[1]
    pn32 = _split64(state.pn)  # [B, N, 2, 2]
    el32 = _split64(state.elapsed)  # [B, 2]
    G = block_ids.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # slots
            pl.BlockSpec(memory_space=pltpu.VMEM),  # added
            pl.BlockSpec(memory_space=pltpu.VMEM),  # taken
            pl.BlockSpec(memory_space=pltpu.VMEM),  # elapsed
            pl.BlockSpec(
                (ROWS_PER_BLOCK, N, 2, 2),
                lambda g, blk, st, en: (blk[g], 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (ROWS_PER_BLOCK, 2),
                lambda g, blk, st, en: (blk[g], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (ROWS_PER_BLOCK, N, 2, 2),
                lambda g, blk, st, en: (blk[g], 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (ROWS_PER_BLOCK, 2),
                lambda g, blk, st, en: (blk[g], 0),
                memory_space=pltpu.VMEM,
            ),
        ],
    )

    pn32, el32 = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(pn32.shape, jnp.int32),
            jax.ShapeDtypeStruct(el32.shape, jnp.int32),
        ],
        # Inputs in flattened order: 0=block_ids, 1=starts, 2=ends, 3=rows,
        # 4=slots, 5=added, 6=taken, 7=elapsed, 8=pn32, 9=el32.
        input_output_aliases={8: 0, 9: 1},
        interpret=interpret,
    )(block_ids, starts, ends, rows, slots, added, taken, elapsed, pn32, el32)

    return LimiterState(pn=_join64(pn32), elapsed=_join64(el32))


def merge_batch_pallas(
    state: LimiterState,
    rows: np.ndarray,
    slots: np.ndarray,
    added_nt: np.ndarray,
    taken_nt: np.ndarray,
    elapsed_ns: np.ndarray,
    interpret: bool = False,
) -> LimiterState:
    """Host entry: sort, plan blocks, launch. Arrays are host numpy; values
    must already be non-negative (ingest clamp)."""
    B = state.pn.shape[0]
    order, block_ids, starts, ends, _ = prepare(np.asarray(rows, np.int64), B)

    def split_host(v) -> np.ndarray:
        v = np.ascontiguousarray(np.asarray(v, np.int64)[order])
        return v.view(np.int32).reshape(len(v), 2)

    return _merge_pallas_device(
        state,
        jnp.asarray(block_ids),
        jnp.asarray(starts),
        jnp.asarray(ends),
        jnp.asarray(np.asarray(rows, np.int32)[order]),
        jnp.asarray(np.asarray(slots, np.int32)[order]),
        jnp.asarray(split_host(added_nt)),
        jnp.asarray(split_host(taken_nt)),
        jnp.asarray(split_host(elapsed_ns)),
        interpret=interpret,
    )


def compile_error(state: LimiterState) -> "str | None":
    """Compile the native (non-interpret) kernel for ``state``'s exact
    shape, dtype and placement, without running it: ``None`` when the
    backend's compiler accepts it, else the compiler's message.

    The engine asks this once, at start, for its real state — a probe at
    some other shape proves nothing about the one the engine serves
    (Mosaic's tiling checks depend on it). Interpret mode exists on CPU
    but is orders of magnitude slower than the XLA scatter, so a CPU
    backend never counts."""
    if jax.default_backend() == "cpu":
        return "the cpu backend has no native pallas lowering"

    def spec(shape, dtype, like=None):
        sharding = getattr(like, "sharding", None)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = jnp.int32
    args = (
        LimiterState(
            pn=spec(state.pn.shape, state.pn.dtype, state.pn),
            elapsed=spec(state.elapsed.shape, state.elapsed.dtype, state.elapsed),
        ),
        spec((1,), i32), spec((1,), i32), spec((1,), i32),
        spec((1,), i32), spec((1,), i32),
        spec((1, 2), i32), spec((1, 2), i32), spec((1, 2), i32),
    )
    try:
        _merge_pallas_device.lower(*args).compile()
    except Exception as exc:  # the compiler's refusal, verbatim
        return f"{type(exc).__name__}: {exc}"
    return None


# auto-mode knobs (PATROL_MERGE_KERNEL=auto): pallas wins when the batch is
# block-sparse — it streams only touched 512-row tiles where the XLA scatter
# serializes per delta. Tiny batches lose to kernel-launch overhead; near-
# dense batches should take the vectorized dense path instead. Thresholds
# are overridable so bench.py's measured crossover can be pinned via env.
import os as _os

AUTO_MIN_BATCH = int(_os.environ.get("PATROL_PALLAS_MIN_BATCH", "1024"))
AUTO_BLOCK_FRAC = float(_os.environ.get("PATROL_PALLAS_BLOCK_FRAC", "0.25"))


def auto_pick(rows: np.ndarray, num_buckets: int) -> bool:
    """The PATROL_MERGE_KERNEL=auto per-batch heuristic, asked only once
    :func:`compile_error` has accepted the kernel for the engine's state:
    use the pallas block-sparse kernel iff the batch is big enough to
    amortize launch and touches a small fraction of the state's 512-row
    blocks."""
    if len(rows) < AUTO_MIN_BATCH:
        return False
    touched = len(np.unique(np.asarray(rows) // ROWS_PER_BLOCK))
    total = max(1, (num_buckets + ROWS_PER_BLOCK - 1) // ROWS_PER_BLOCK)
    return touched <= total * AUTO_BLOCK_FRAC
