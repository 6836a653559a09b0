"""Mesh scale-out: the TPU-native replacement for scaling by adding UDP
peers (SURVEY §2 "Parallelism & distribution strategies").

The reference has exactly two scaling axes (SURVEY §5): bucket cardinality
and node count. They map onto a 2-D ``jax.sharding.Mesh``:

* axis ``"b"`` — **bucket sharding**: the bucket dimension of
  ``pn[B, N, 2]`` / ``elapsed[B]`` is partitioned across devices; takes and
  merges for a bucket run only on the shard that owns its rows (host
  routing, no cross-device traffic on the hot path).
* axis ``"r"`` — **replication**: full state replicas that each ingest a
  partition of the incoming take/merge stream and converge with one
  max all-reduce per step. This is Patrol's UDP broadcast re-expressed as an
  ICI collective — the 256-byte-datagram protocol (repo.go:123-158) becomes
  an elementwise int64 max across the mesh, five orders of magnitude more
  bandwidth.

Correctness of max-convergence relies on two invariants:

1. All CRDT planes are monotone (PN lanes and the elapsed G-counter only
   grow), so elementwise max is a join and convergence is exact.
2. Each bucket row has one *home replica* (``row % R``) that applies its
   takes; other replicas receive the result via the max all-reduce. Two
   replicas incrementing the same lane concurrently would race like the
   reference's lossy scalar merge (SURVEY §2, known bug) — home routing
   makes the write single-writer per lane while reads/merges stay
   everywhere.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from patrol_tpu.models.limiter import LimiterConfig, LimiterState
from patrol_tpu.ops.merge import MergeBatch, merge_batch
from patrol_tpu.ops.take import TakeRequest, TakeResult, take_batch

REPLICA_AXIS = "r"
BUCKET_AXIS = "b"



def make_mesh(replicas: int = 1, devices=None) -> Mesh:
    """A (replicas × shards) mesh over the available devices. ``replicas``
    must divide the device count; the remainder becomes the bucket axis."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n % replicas:
        raise ValueError(f"{replicas} replicas do not divide {n} devices")
    grid = np.array(devices).reshape(replicas, n // replicas)
    return Mesh(grid, (REPLICA_AXIS, BUCKET_AXIS))


# State: bucket axis sharded over "b", replicated over "r".
STATE_SPEC = LimiterState(pn=P(BUCKET_AXIS, None, None), elapsed=P(BUCKET_AXIS))
# Request/delta batches: leading dim laid out as (replica-major, shard-minor)
# blocks, partitioned over both axes.
BATCH_SPEC = P((REPLICA_AXIS, BUCKET_AXIS))


def state_sharding(mesh: Mesh) -> LimiterState:
    return LimiterState(
        pn=NamedSharding(mesh, STATE_SPEC.pn),
        elapsed=NamedSharding(mesh, STATE_SPEC.elapsed),
    )


def place_state(state: LimiterState, mesh: Mesh) -> LimiterState:
    """Shard an existing state onto the mesh (bucket rows split across
    ``"b"``, replicated across ``"r"``)."""
    sh = state_sharding(mesh)
    return LimiterState(
        pn=jax.device_put(state.pn, sh.pn),
        elapsed=jax.device_put(state.elapsed, sh.elapsed),
    )


def init_sharded_state(config: LimiterConfig, mesh: Mesh) -> LimiterState:
    sh = state_sharding(mesh)
    return LimiterState(
        pn=jnp.zeros((config.buckets, config.nodes, 2), jnp.int64, device=sh.pn),
        elapsed=jnp.zeros((config.buckets,), jnp.int64, device=sh.elapsed),
    )


def _allreduce_max(x: jax.Array) -> jax.Array:
    """FLAT max all-reduce over the replica axis, expressed as all_gather +
    local max: real TPU compile paths (v5e AOT, BENCH r2) reject non-Sum
    s64 all-reduces ("Supported lowering only of Sum all reduce") while
    all-gather lowers everywhere. One replica step's extra HBM is
    replicas × block, transient, and XLA fuses the reduction. Kept as the
    fallback converge (non-power-of-two replica counts) and as the
    reference the tree path is checked bit-exact against."""
    g = jax.lax.all_gather(x, REPLICA_AXIS)
    return jnp.max(g, axis=0)


def _tree_allreduce_max(x: jax.Array, replicas: int) -> jax.Array:
    """Hierarchical tree max-reduce over the replica axis (Tascade's
    coalescing-reduction shape, arXiv:2311.15810): log2(R) rounds of
    recursive doubling — each round every replica exchanges its partial
    join with the partner at XOR distance 2^k (``ppermute``, point-to-
    point over ICI) and max-joins it locally, so interior "nodes" RE-FOLD
    before forwarding. Total traffic is R·log2(R) blocks versus the flat
    all_gather's R·(R−1), and each round moves one block per link instead
    of gathering the whole replica set — at R=8 that is 24 vs 56 blocks,
    and the gap widens superlinearly with R. Exactness is free: max is
    associative/commutative/idempotent, so ANY reduction tree computes
    the same join bit-for-bit (the delta-CRDT composition result,
    arXiv:1410.2803) — machine-checked by the registered
    :func:`tree_reduce_states` prove root and pinned on-device by
    tests/test_topology.py's tree-vs-flat equality.

    Requires a power-of-two ``replicas`` (the butterfly pairing);
    :func:`converge` falls back to the flat path otherwise. ``ppermute``
    is pure data movement, so the v5e "Sum all reduce only" s64 lowering
    restriction (BENCH r2) does not apply."""
    step = 1
    while step < replicas:
        perm = [(i, i ^ step) for i in range(replicas)]
        peer = jax.lax.ppermute(x, REPLICA_AXIS, perm=perm)
        x = jnp.maximum(x, peer)
        step <<= 1
    return x


def tree_join_states(a: LimiterState, b: LimiterState) -> LimiterState:
    """The tree's interior-node join: elementwise max of both CRDT
    planes — one node of the converge tree, host-callable for tests."""
    return LimiterState(
        pn=jnp.maximum(a.pn, b.pn),
        elapsed=jnp.maximum(a.elapsed, b.elapsed),
    )


def tree_reduce_states(pn: jax.Array, elapsed: jax.Array) -> LimiterState:
    """Pure (collective-free) twin of the converge tree, THE registered
    prove root (``parallel.topology.tree_reduce_states``): reduce R
    stacked replica states (``pn[R, B, N, 2]``, ``elapsed[R, B]``) with
    exactly the butterfly schedule :func:`_tree_allreduce_max` runs over
    ICI — level k joins index i with index i XOR 2^k. patrol-prove
    traces it (PTP001/PTP005) and model-checks flat-vs-tree equivalence,
    permutation independence, duplicate-leaf idempotence, and
    monotonicity over enumerated lattice domains (PTP002-004) — the
    distributed path inherits the argument because the schedule is the
    same join tree. Non-power-of-two R folds flat (the fallback
    :func:`converge` takes on hardware)."""
    r = pn.shape[0]
    if r > 1 and r & (r - 1) == 0:
        step = 1
        while step < r:
            idx = jnp.arange(r, dtype=jnp.int32) ^ step
            pn = jnp.maximum(pn, pn[idx])
            elapsed = jnp.maximum(elapsed, elapsed[idx])
            step <<= 1
        return LimiterState(pn=pn[0], elapsed=elapsed[0])
    return LimiterState(pn=jnp.max(pn, axis=0), elapsed=jnp.max(elapsed, axis=0))


def converge(state: LimiterState, replicas: Optional[int] = None) -> LimiterState:
    """Cross-replica CvRDT join over ICI — the collective that replaces the
    reference's per-take UDP fan-out (repo.go:129-158). With a static
    power-of-two ``replicas`` (the builders thread it from the mesh), the
    join runs as a hierarchical tree reduce; otherwise the flat
    all_gather+max fallback (bit-identical by the join laws)."""
    if replicas is not None and replicas > 1 and replicas & (replicas - 1) == 0:
        return LimiterState(
            pn=_tree_allreduce_max(state.pn, replicas),
            elapsed=_tree_allreduce_max(state.elapsed, replicas),
        )
    return LimiterState(
        pn=_allreduce_max(state.pn),
        elapsed=_allreduce_max(state.elapsed),
    )


def cluster_step(
    state: LimiterState,
    deltas: MergeBatch,
    reqs: TakeRequest,
    node_slot: int,
    replicas: Optional[int] = None,
) -> Tuple[LimiterState, TakeResult]:
    """One SPMD update step, per (replica, shard) block: merge this block's
    replication deltas, apply this block's takes, converge replicas.

    Rows in ``reqs``/``deltas`` are SHARD-LOCAL indices; the host router
    (:func:`route_requests`) guarantees each take sits in its home
    (replica, shard) block and every other block carries padding."""
    state = merge_batch(state, deltas)
    state, res = take_batch(state, reqs, node_slot)
    state = converge(state, replicas)
    return state, res


def build_cluster_step(mesh: Mesh, node_slot: int):
    """jit(shard_map(cluster_step)) over the mesh, with donated state."""
    fn = jax.shard_map(
        partial(
            cluster_step,
            node_slot=node_slot,
            replicas=mesh.shape[REPLICA_AXIS],
        ),
        mesh=mesh,
        in_specs=(
            STATE_SPEC,
            MergeBatch(*(BATCH_SPEC,) * 5),
            TakeRequest(*(BATCH_SPEC,) * 8),
        ),
        out_specs=(STATE_SPEC, TakeResult(*(BATCH_SPEC,) * 7)),
        # converge() replicates its outputs by VALUE (tree reduce or
        # all_gather over the replica axis — every replica computes the
        # identical join), but the static varying-axes checker can only
        # prove replication for collectives like pmax, which the v5e AOT
        # compile path rejects for s64 ("Supported lowering only of Sum
        # all reduce", BENCH r2). Replication is instead asserted by
        # tests/test_topology.py's cross-replica equality checks.
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=0)


# Packed-matrix layouts for the staged mesh step (the device-commit
# pipeline's transfer shape, PR 5): ONE int64[8, B·k] take matrix and ONE
# int64[5, B·k] merge matrix per dispatch instead of 13 little arrays —
# per-array transfer setup dominates host→device latency on this stack,
# and a single matrix can ride a reusable StagingPool buffer.
TAKE_MAT_ROWS = 8  # rows, now_ns, freq, per_ns, count_nt, nreq, cap, created
MERGE_MAT_ROWS = 5  # rows, slots, added_nt, taken_nt, elapsed_ns


def batch_sharding(mesh: Mesh):
    """NamedSharding for the packed matrices: field dim replicated, the
    block dim split (replica-major, shard-minor) over both mesh axes."""
    return NamedSharding(mesh, P(None, (REPLICA_AXIS, BUCKET_AXIS)))


def build_cluster_step_packed(mesh: Mesh, node_slot: int):
    """jit(shard_map(...)) over the mesh taking the PACKED matrices:
    ``(state, take_mat[8, B·k_t], merge_mat[5, B·k_m])`` →
    ``(state, out[7, B·k_t])`` with donated state — merge + take +
    tree-converge fused in one dispatch, unpacking on-device so the host
    ships exactly two staged transfers per tick (no host round-trips
    between the three phases). ``out`` rows mirror the single-device
    ``_jit_take_packed`` result stack: have_nt, admitted, own_added_nt,
    own_taken_nt, elapsed_ns, sum_added_nt, sum_taken_nt."""
    replicas = mesh.shape[REPLICA_AXIS]
    mat_spec = P(None, (REPLICA_AXIS, BUCKET_AXIS))

    def step(state, take_mat, merge_mat):
        mb = MergeBatch(
            rows=merge_mat[0].astype(jnp.int32),
            slots=merge_mat[1].astype(jnp.int32),
            added_nt=merge_mat[2],
            taken_nt=merge_mat[3],
            elapsed_ns=merge_mat[4],
        )
        req = TakeRequest(
            rows=take_mat[0].astype(jnp.int32),
            now_ns=take_mat[1],
            freq=take_mat[2],
            per_ns=take_mat[3],
            count_nt=take_mat[4],
            nreq=take_mat[5],
            cap_base_nt=take_mat[6],
            created_ns=take_mat[7],
        )
        state, res = cluster_step(
            state, mb, req, node_slot=node_slot, replicas=replicas
        )
        out = jnp.stack(
            [
                res.have_nt,
                res.admitted,
                res.own_added_nt,
                res.own_taken_nt,
                res.elapsed_ns,
                res.sum_added_nt,
                res.sum_taken_nt,
            ]
        )
        return state, out

    fn = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(STATE_SPEC, mat_spec, mat_spec),
        out_specs=(STATE_SPEC, mat_spec),
        # See build_cluster_step: converge() replicates by value.
        check_vma=False,
    )
    return jax.jit(fn, donate_argnums=0)


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Host-side routing geometry for a mesh deployment."""

    replicas: int
    shards: int
    rows_per_shard: int

    @property
    def blocks(self) -> int:
        return self.replicas * self.shards

    def locate(self, global_row: int) -> Tuple[int, int, int]:
        """→ (home_replica, shard, local_row) for a bucket row."""
        shard, local_row = divmod(global_row, self.rows_per_shard)
        return global_row % self.replicas, shard, local_row

    def block_index(self, replica: int, shard: int) -> int:
        return replica * self.shards + shard


def plan_for(mesh: Mesh, config: LimiterConfig) -> MeshPlan:
    shards = mesh.shape[BUCKET_AXIS]
    if config.buckets % shards:
        raise ValueError(f"{shards} shards do not divide {config.buckets} buckets")
    return MeshPlan(
        replicas=mesh.shape[REPLICA_AXIS],
        shards=shards,
        rows_per_shard=config.buckets // shards,
    )


def route_requests(
    plan: MeshPlan,
    takes,  # sequence of (global_row, now_ns, freq, per_ns, count_nt, nreq, cap_base_nt, created_ns)
    deltas,  # sequence of (global_row, slot, added_nt, taken_nt, elapsed_ns)
    k_take: int,
    k_merge: int,
    deltas_to_home: bool = False,
) -> Tuple[TakeRequest, MergeBatch]:
    """Pack host requests into the (replica-major, shard-minor) block layout
    consumed by :func:`build_cluster_step`. Each take lands in its home
    block; deltas spread round-robin over replicas (merges are idempotent,
    any replica may ingest them) unless ``deltas_to_home`` — then a delta
    lands on its row's home replica, making it visible to same-step takes
    (useful for deterministic tests and lowest staleness). Overflowing a
    block raises — the caller batches accordingly."""
    take_mat, merge_mat, _placed = route_packed(
        plan, takes, deltas, k_take, k_merge, deltas_to_home=deltas_to_home
    )
    return (
        TakeRequest(
            rows=jnp.asarray(take_mat[0], jnp.int32),
            now_ns=jnp.asarray(take_mat[1]),
            freq=jnp.asarray(take_mat[2]),
            per_ns=jnp.asarray(take_mat[3]),
            count_nt=jnp.asarray(take_mat[4]),
            nreq=jnp.asarray(take_mat[5]),
            cap_base_nt=jnp.asarray(take_mat[6]),
            created_ns=jnp.asarray(take_mat[7]),
        ),
        MergeBatch(
            rows=jnp.asarray(merge_mat[0], jnp.int32),
            slots=jnp.asarray(merge_mat[1], jnp.int32),
            added_nt=jnp.asarray(merge_mat[2]),
            taken_nt=jnp.asarray(merge_mat[3]),
            elapsed_ns=jnp.asarray(merge_mat[4]),
        ),
    )


def delta_block_assignment(
    plan: MeshPlan, rows_a: np.ndarray, deltas_to_home: bool = False
) -> np.ndarray:
    """The delta→block routing rule, exposed so callers that sub-tick a
    drain (MeshEngine) can compute per-block fills BEFORE packing:
    shard from the row, replica round-robin by arrival index (merges are
    idempotent joins — any replica may ingest, converge spreads them) or
    the row's home replica with ``deltas_to_home``."""
    K = len(rows_a)
    shard = rows_a // plan.rows_per_shard
    replica = (
        rows_a % plan.replicas
        if deltas_to_home
        else np.arange(K, dtype=np.int64) % plan.replicas
    )
    return replica * plan.shards + shard


def route_packed(
    plan: MeshPlan,
    takes,
    deltas,
    k_take: int,
    k_merge: int,
    take_out: Optional[np.ndarray] = None,
    merge_out: Optional[np.ndarray] = None,
    deltas_to_home: bool = False,
    delta_blocks: Optional[np.ndarray] = None,
):
    """Packing core shared by :func:`route_requests` and the MeshEngine's
    staged tick: fills (or allocates) the int64 ``[TAKE_MAT_ROWS, B·k_take]``
    / ``[MERGE_MAT_ROWS, B·k_merge]`` matrices in block layout and returns
    ``(take_mat, merge_mat, placed)`` where ``placed`` is the
    ``(block, slot-in-block)`` of each take in input order (the completion
    path's result indices). Caller-leased ``*_out`` buffers (StagingPool)
    are zeroed first — padding entries MUST read as no-ops."""
    B = plan.blocks
    if take_out is None:
        take_mat = np.zeros((TAKE_MAT_ROWS, B * k_take), dtype=np.int64)
    else:
        take_mat = take_out
        take_mat[:] = 0
    if merge_out is None:
        merge_mat = np.zeros((MERGE_MAT_ROWS, B * k_merge), dtype=np.int64)
    else:
        merge_mat = merge_out
        merge_mat[:] = 0

    placed: list = []
    fill_t = [0] * B
    for row, now_ns, freq, per_ns, count_nt, nreq, cap_base_nt, created_ns in takes:
        replica, shard, local = plan.locate(row)
        blk = plan.block_index(replica, shard)
        i = fill_t[blk]
        if i >= k_take:
            raise ValueError(f"take block {blk} overflow (k_take={k_take})")
        at = blk * k_take + i
        take_mat[0, at] = local
        take_mat[1, at] = now_ns
        take_mat[2, at] = freq
        take_mat[3, at] = per_ns
        take_mat[4, at] = count_nt
        take_mat[5, at] = nreq
        take_mat[6, at] = cap_base_nt
        take_mat[7, at] = created_ns
        fill_t[blk] += 1
        placed.append((blk, i))

    # Deltas pack vectorized — thousands per tick ride this path (takes
    # are pre-coalesced to a few keys, so their loop stays Python).
    # ``deltas`` is a 5-tuple of int64 arrays (rows, slots, added_nt,
    # taken_nt, elapsed_ns) or a sequence of 5-tuples (tests).
    if deltas is not None and len(deltas):
        if isinstance(deltas, tuple) and isinstance(deltas[0], np.ndarray):
            rows_a, slots_a, added_a, taken_a, elapsed_a = (
                np.asarray(x, dtype=np.int64) for x in deltas
            )
        else:
            arr = np.asarray(list(deltas), dtype=np.int64).T
            rows_a, slots_a, added_a, taken_a, elapsed_a = arr
        K = len(rows_a)
        local = rows_a % plan.rows_per_shard
        blk = (
            delta_blocks
            if delta_blocks is not None
            else delta_block_assignment(plan, rows_a, deltas_to_home)
        )
        counts = np.bincount(blk, minlength=B)
        if counts.max(initial=0) > k_merge:
            raise ValueError(
                f"merge block {int(counts.argmax())} overflow (k_merge={k_merge})"
            )
        order = np.argsort(blk, kind="stable")
        sblk = blk[order]
        run_start = np.concatenate(([0], np.cumsum(counts)))[sblk]
        at = sblk * k_merge + (np.arange(K, dtype=np.int64) - run_start)
        merge_mat[0, at] = local[order]
        merge_mat[1, at] = slots_a[order]
        merge_mat[2, at] = np.maximum(added_a[order], 0)
        merge_mat[3, at] = np.maximum(taken_a[order], 0)
        merge_mat[4, at] = np.maximum(elapsed_a[order], 0)

    return take_mat, merge_mat, placed
