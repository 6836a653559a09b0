"""The kernel-certification kit: every limiter lattice family the repo
ships is registered HERE as one declarative :class:`KernelFamily`
record — its proof obligations (``analysis/prove.py``, stage 4), its
native-ABI twins (stage 5), its protocol-model hook (stage 6), its
linearizability spec (stage 8), its wire codec, its bench smoke fields,
and the seeded mutations the stack must demonstrably reject (stage 9,
``analysis/cert.py``, PTK001-005).

The registry lives next to the kernels, for the same reason lint.py
keeps its allowlists at the top of the file: adding a kernel without
declaring its obligations — or weakening an obligation — is a diff on
this file, in code review's line of sight. The cert stage closes the
remaining gap: a family that declares itself but never reaches a
checking stage (PTK001), a seeded mutation the stack fails to reject
with the exact registered code (PTK002), an obligation declared absent
without a written justification (PTK003), or a jitted lattice kernel in
ops/ registered in no family at all (PTK004) is each a finding.

Per prove root (unchanged semantics from the flat-registry era):

* the **tracer** builds the abstract shapes the kernel is traced over
  (``jax.make_jaxpr`` — shapes are tiny; the IR is shape-polymorphic in
  all the ways that matter to the lattice structure);
* ``structural`` picks the PTP001 profile — ``"join"`` for CvRDT joins
  (state planes may only flow through max/scatter-max and layout ops),
  ``"callbacks"`` for delta-side kernels whose local adds are the point
  (take's greedy admission; scalar merge's deficit attribution);
* ``model`` names the exhaustive small-domain suite: every declared
  algebraic obligation (commutes / idempotent / monotone) is checked
  bit-exactly over an enumerated tiny lattice.

The flat ``PROVE_ROOTS`` / ``LIN_SPECS`` / ``ABI_OBLIGATIONS`` tuples
the stage drivers and tests consume are DERIVED from the family records
at the bottom of this file — same names, same entries, one source of
truth.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from patrol_tpu.analysis.abi import AbiObligation
from patrol_tpu.analysis.linearizability import LinSpecFamily
from patrol_tpu.analysis.protocol import ConcLaws, GcraLaws, QuotaLaws
from patrol_tpu.analysis.prove import JOIN_BATCH_ADAPTERS, ProveRoot, Trace
from patrol_tpu.models.limiter import ADDED, TAKEN, LimiterState
from patrol_tpu.ops.commit import CommitBlocks
from patrol_tpu.ops.delta import DeltaBatch
from patrol_tpu.ops.merge import FoldedMergeBatch, MergeBatch, RowDenseBatch

_S = jax.ShapeDtypeStruct
_B, _N, _K = 4, 2, 3  # declared abstract shapes for tracing


def _state() -> LimiterState:
    return LimiterState(
        pn=_S((_B, _N, 2), jnp.int64), elapsed=_S((_B,), jnp.int64)
    )


def _vec(dtype, k: int = _K):
    return _S((k,), dtype)


def _mk_trace(fn, *args, n_state_in=2, n_state_out=2, shapes_match=True) -> Trace:
    closed = jax.make_jaxpr(fn)(*args)
    return Trace(
        closed,
        range(n_state_in),
        range(n_state_out),
        shapes_must_match=shapes_match,
    )


def _trace_merge_batch(fn) -> Trace:
    batch = MergeBatch(
        rows=_vec(jnp.int32),
        slots=_vec(jnp.int32),
        added_nt=_vec(jnp.int64),
        taken_nt=_vec(jnp.int64),
        elapsed_ns=_vec(jnp.int64),
    )
    return _mk_trace(fn, _state(), batch)


def _trace_merge_batch_folded(fn) -> Trace:
    batch = FoldedMergeBatch(
        rows=_vec(jnp.int32),
        slots=_vec(jnp.int32),
        added_nt=_vec(jnp.int64),
        taken_nt=_vec(jnp.int64),
        erows=_vec(jnp.int32),
        elapsed_ns=_vec(jnp.int64),
    )
    return _mk_trace(fn, _state(), batch)


def _trace_commit_blocks(fn) -> Trace:
    # Two-block ring: the commit kernel's shape class is [J, K], and a
    # J > 1 trace pins the flatten-then-scatter structure the block ring
    # relies on (a J=1 trace would also pass for a per-block loop).
    def _mat(dtype):
        return _S((2, _K), dtype)

    blocks = CommitBlocks(
        rows=_mat(jnp.int32),
        slots=_mat(jnp.int32),
        added_nt=_mat(jnp.int64),
        taken_nt=_mat(jnp.int64),
        erows=_mat(jnp.int32),
        elapsed_ns=_mat(jnp.int64),
    )
    return _mk_trace(fn, _state(), blocks)


def _trace_delta_fold(fn) -> Trace:
    batch = DeltaBatch(
        rows=_vec(jnp.int32),
        slots=_vec(jnp.int32),
        added_nt=_vec(jnp.int64),
        taken_nt=_vec(jnp.int64),
        elapsed_ns=_vec(jnp.int64),
    )
    return _mk_trace(fn, _state(), batch)


def _trace_decode_fold_raw(fn) -> Trace:
    # Tiny raw planes: 2 packets × 128-byte rows (max_entries(128) = 2).
    # State invars are the leading (pn, elapsed) pair; the state outputs
    # lead the verdict/decoded-field outputs, so indices (0, 1) hold on
    # both sides.
    from patrol_tpu.ops.ingest import max_entries

    e = max_entries(128)
    return _mk_trace(
        fn,
        _state(),
        _S((2, 128), jnp.uint8),
        _S((2,), jnp.int32),
        _S((2, e), jnp.int32),  # entry_off (the host framing proposal)
        _S((2, e), jnp.int32),  # rows (the host directory plan)
        _S((2, e), jnp.bool_),
    )


def _trace_merge_rows_dense(fn) -> Trace:
    batch = RowDenseBatch(
        rows=_vec(jnp.int32),
        updates=_S((_K, _N, 2), jnp.int64),
        elapsed_ns=_vec(jnp.int64),
    )
    return _mk_trace(fn, _state(), batch)


def _trace_merge_dense(fn) -> Trace:
    return _mk_trace(fn, _state(), _state())


_R = 2  # traced replica fan-in: power of two ⇒ the butterfly (tree) path


def _trace_tree_converge(fn) -> Trace:
    # Stacked replica planes in, one converged state out: both invars are
    # state-tainted; the leading R dim disappears, so shapes don't match.
    return _mk_trace(
        fn,
        _S((_R, _B, _N, 2), jnp.int64),
        _S((_R, _B), jnp.int64),
        shapes_match=False,
    )


def _trace_read_rows(fn) -> Trace:
    return _mk_trace(fn, _state(), _vec(jnp.int32), shapes_match=False)


def _trace_scalar_batch(fn) -> Trace:
    batch = MergeBatch(
        rows=_vec(jnp.int32),
        slots=_vec(jnp.int32),
        added_nt=_vec(jnp.int64),
        taken_nt=_vec(jnp.int64),
        elapsed_ns=_vec(jnp.int64),
    )
    return _mk_trace(fn, _state(), batch)


def _trace_take_batch(fn) -> Trace:
    from patrol_tpu.ops.take import TakeRequest

    req = TakeRequest(
        rows=_vec(jnp.int32),
        now_ns=_vec(jnp.int64),
        freq=_vec(jnp.int64),
        per_ns=_vec(jnp.int64),
        count_nt=_vec(jnp.int64),
        nreq=_vec(jnp.int64),
        cap_base_nt=_vec(jnp.int64),
        created_ns=_vec(jnp.int64),
    )
    return _mk_trace(lambda s, r: fn(s, r, 1), _state(), req)


def _trace_take_n_batch(fn) -> Trace:
    from patrol_tpu.ops.take import TAKE_PACK_ROWS

    # The feeder's exact transfer layout: ONE int64[TAKE_PACK_ROWS, K]
    # request matrix (the coalesced nreq row included). State planes
    # lead both sides, so the default (0, 1) indices hold.
    return _mk_trace(
        lambda s, p: fn(s, p, 1),
        _state(),
        _S((TAKE_PACK_ROWS, _K), jnp.int64),
    )


def _trace_lifecycle_probe(fn) -> Trace:
    from patrol_tpu.ops.lifecycle import LifecycleProbe

    probe = LifecycleProbe(
        rows=_vec(jnp.int32),
        now_ns=_vec(jnp.int64),
        per_ns=_vec(jnp.int64),
        cap_base_nt=_vec(jnp.int64),
        created_ns=_vec(jnp.int64),
    )
    # Pure read: both state planes are taint sources, NO state outputs —
    # the probe structurally cannot mutate limiter state (the strongest
    # form of the PTP005 stability claim for a GC predicate).
    return _mk_trace(
        lambda s, p: fn(s, p, 1), _state(), probe,
        n_state_out=0, shapes_match=False,
    )


def _trace_gcra_take(fn) -> Trace:
    from patrol_tpu.ops.gcra import GcraRequest

    req = GcraRequest(
        rows=_vec(jnp.int32),
        now_ns=_vec(jnp.int64),
        emission_ns=_vec(jnp.int64),
        tol_ns=_vec(jnp.int64),
        nreq=_vec(jnp.int64),
    )
    return _mk_trace(lambda s, r: fn(s, r, 1), _state(), req)


def _trace_conc_acquire(fn) -> Trace:
    from patrol_tpu.ops.concurrency import ConcRequest

    req = ConcRequest(
        rows=_vec(jnp.int32),
        limit_nt=_vec(jnp.int64),
        count_nt=_vec(jnp.int64),
        nreq=_vec(jnp.int64),
        releases=_vec(jnp.int64),
    )
    return _mk_trace(lambda s, r: fn(s, r, 1), _state(), req)


def _trace_quota_take(fn) -> Trace:
    from patrol_tpu.ops.hierquota import QuotaRequest

    req = QuotaRequest(
        rows_global=_vec(jnp.int32),
        rows_tenant=_vec(jnp.int32),
        rows_user=_vec(jnp.int32),
        limit_global_nt=_vec(jnp.int64),
        limit_tenant_nt=_vec(jnp.int64),
        limit_user_nt=_vec(jnp.int64),
        count_nt=_vec(jnp.int64),
        nreq=_vec(jnp.int64),
    )
    return _mk_trace(lambda s, r: fn(s, r, 1), _state(), req)


# --- join-batch adapters: single (row, slot, added, taken, elapsed) lattice
# deltas → each kernel's batch type, K=1 (registered for the model checker).


def _as_merge_batch(d) -> MergeBatch:
    return MergeBatch(
        rows=d[0].astype(jnp.int32)[None],
        slots=d[1].astype(jnp.int32)[None],
        added_nt=d[2][None],
        taken_nt=d[3][None],
        elapsed_ns=d[4][None],
    )


def _as_folded_batch(d) -> FoldedMergeBatch:
    # K=1: the sorted/unique flags the kernel asserts are trivially true.
    return FoldedMergeBatch(
        rows=d[0].astype(jnp.int32)[None],
        slots=d[1].astype(jnp.int32)[None],
        added_nt=d[2][None],
        taken_nt=d[3][None],
        erows=d[0].astype(jnp.int32)[None],
        elapsed_ns=d[4][None],
    )


def _as_commit_blocks(d) -> CommitBlocks:
    # J=1, K=1 ring: the asserted sorted/unique flags are trivially true,
    # and the model checker's order/duplication grids become exactly the
    # cross-block coalesce-order question (blocks are delta sets).
    return CommitBlocks(
        rows=d[0].astype(jnp.int32)[None, None],
        slots=d[1].astype(jnp.int32)[None, None],
        added_nt=d[2][None, None],
        taken_nt=d[3][None, None],
        erows=d[0].astype(jnp.int32)[None, None],
        elapsed_ns=d[4][None, None],
    )


def _as_delta_batch(d) -> DeltaBatch:
    return DeltaBatch(
        rows=d[0].astype(jnp.int32)[None],
        slots=d[1].astype(jnp.int32)[None],
        added_nt=d[2][None],
        taken_nt=d[3][None],
        elapsed_ns=d[4][None],
    )


def _as_rows_dense_batch(d) -> RowDenseBatch:
    # One-hot lane window: the delta's (added, taken) in its slot, zeros —
    # the join identity on the non-negative domain — everywhere else.
    upd = jnp.zeros((1, _N, 2), jnp.int64).at[0, d[1]].set(
        jnp.stack([d[2], d[3]])
    )
    return RowDenseBatch(
        rows=d[0].astype(jnp.int32)[None], updates=upd, elapsed_ns=d[4][None]
    )


JOIN_BATCH_ADAPTERS.update(
    merge_batch=_as_merge_batch,
    folded=_as_folded_batch,
    rows_dense=_as_rows_dense_batch,
    commit_blocks=_as_commit_blocks,
    delta_fold=_as_delta_batch,
)

_ALL = ("PTP001", "PTP002", "PTP003", "PTP004", "PTP005")


# ---------------------------------------------------------------------------
# The certification record types.


@dataclasses.dataclass(frozen=True)
class CertMutation:
    """One seeded mutation a family registers: a deliberately broken
    variant of the family's semantics that the checking stack MUST
    reject with ``expect`` (the exact PT code, pinned — a mutation that
    trips a *different* code means the check that was supposed to own
    this hazard has gone soft).

    ``stage`` selects the executor (``analysis/cert.py``):

    * ``"prove"`` — ``mutant`` is a drop-in replacement kernel;
      executed via ``prove_root(root, fn=mutant)`` against the family
      root named by ``target``.
    * ``"protocol"`` with ``laws`` — a family-law payload; executed via
      ``protocol.FAMILY_CHECKS[target](laws=laws)``.
    * ``"protocol"`` without ``laws`` — a reference to a legacy
      ``protocol.MUTATIONS`` entry named ``target``; cert re-executes
      it through ``check_protocol`` and pins the code.
    * ``"lin"`` — a reference to a ``linearizability.LIN_MUTATIONS``
      entry named ``target``; cert checks registration + that the
      registered expect matches (stage 8 executes the schedule suite —
      re-running the full enumeration per cert pass would double the
      gate's cost for no extra signal).
    """

    name: str
    stage: str  # "prove" | "protocol" | "lin"
    target: str
    expect: str
    note: str = ""
    mutant: Optional[Callable] = None  # stage="prove" payload
    laws: Optional[object] = None  # stage="protocol" family-law payload


@dataclasses.dataclass(frozen=True)
class KernelFamily:
    """One certified lattice family: the full declarative record the
    cert meta-checker (stage 9) walks.

    ``absent`` carries the REQUIRED justification strings for every
    obligation code a prove root deliberately does not declare, keyed
    ``"<root-name>:<code>"`` — PTK003 rejects a missing code with no
    justification AND a stale justification for a code the root in fact
    declares. ``*_exempt`` fields likewise carry justifications for a
    whole stage the family doesn't reach (empty string = not exempt,
    the stage is required)."""

    name: str
    domain: str  # the lattice, in one line
    prove_roots: Tuple[ProveRoot, ...]
    absent: Mapping[str, str] = dataclasses.field(default_factory=dict)
    lin_specs: Tuple[LinSpecFamily, ...] = ()
    lin_exempt: str = ""
    protocol: Optional[str] = None  # protocol.FAMILY_CHECKS key
    protocol_exempt: str = ""
    abi: Tuple[AbiObligation, ...] = ()
    wire_codec: Optional[str] = None  # ProveRoot.name of the codec root
    bench_fields: Tuple[str, ...] = ()  # literals bench.py must emit
    bench_exempt: str = ""
    mutations: Tuple[CertMutation, ...] = ()
    mutations_exempt: str = ""
    note: str = ""


def _codec_absent(root_name: str) -> Dict[str, str]:
    """The shared absence record for host-side wire codec roots: pure
    Python byte codecs have no jaxpr to lint (PTP001/PTP005), no lattice
    algebra of their own (PTP002/PTP004) — round-trip exactness PTP003
    is the whole contract."""
    why_py = "host-side python codec: no jaxpr, nothing to trace"
    why_alg = (
        "codecs carry lattice coordinates but compute no joins; "
        "PTP003 round-trip exactness is the entire obligation"
    )
    return {
        f"{root_name}:PTP001": why_py,
        f"{root_name}:PTP002": why_alg,
        f"{root_name}:PTP004": why_alg,
        f"{root_name}:PTP005": why_py,
    }


# ---------------------------------------------------------------------------
# Seeded prove-stage mutants (PTK002 payloads). Each is a full drop-in
# copy of its kernel with exactly one seeded defect — the
# family-specific CRDT hazard its docstring names — and each must be
# rejected by the family's model suite with exactly PTP002.


def _mutant_gcra_window_off_by_one(state, req, node_slot):
    """gcra_take_batch with the conformance window widened by one
    emission interval: admits a burst of burst+1."""
    from patrol_tpu.ops.gcra import GcraResult

    i64 = jnp.int64
    rows = req.rows
    pn_rows = state.pn[rows]
    own_tat = pn_rows[:, node_slot, TAKEN]
    tat = pn_rows[:, :, TAKEN].max(axis=-1)

    base = jnp.maximum(tat, req.now_ns)
    deadline = req.now_ns + req.tol_ns + req.emission_ns  # SEEDED defect
    conforms = tat <= deadline

    safe_t = jnp.where(req.emission_ns <= 0, 1, req.emission_ns)
    extras = jnp.maximum(deadline - base, i64(0)) // safe_t
    k = jnp.where(conforms, 1 + extras, 0)
    k = jnp.where(req.emission_ns > 0, k, 0)
    k = jnp.clip(k, 0, req.nreq)

    new_own = jnp.where(k >= 1, base + k * req.emission_ns, own_tat)
    pn = state.pn.at[rows, node_slot, TAKEN].max(new_own)

    tat_out = jnp.maximum(tat, new_own)
    result = GcraResult(
        admitted=k,
        tat_ns=tat_out,
        own_tat_ns=jnp.maximum(own_tat, new_own),
        allow_at_ns=tat_out - req.tol_ns,
    )
    return LimiterState(pn=pn, elapsed=state.elapsed), result


def _mutant_conc_release_unclamped(state, req, node_slot):
    """conc_acquire_batch without the own-lane release clamp: a phantom
    release drives ADDED past TAKEN and the cluster over-admits
    forever."""
    from patrol_tpu.ops.concurrency import ConcResult

    i64 = jnp.int64
    rows = req.rows
    pn_rows = state.pn[rows]
    own_added = pn_rows[:, node_slot, ADDED]
    own_taken = pn_rows[:, node_slot, TAKEN]
    sum_added = pn_rows[:, :, ADDED].sum(axis=-1)
    sum_taken = pn_rows[:, :, TAKEN].sum(axis=-1)

    want_rel = jnp.maximum(req.releases, i64(0)) * jnp.maximum(
        req.count_nt, i64(0)
    )
    d_rel = want_rel  # SEEDED defect: clamp dropped

    inflight = sum_taken - (sum_added + d_rel)
    headroom = req.limit_nt - inflight
    safe_count = jnp.where(req.count_nt <= 0, 1, req.count_nt)
    k = jnp.clip(headroom // safe_count, 0, req.nreq)
    k = jnp.where(req.count_nt > 0, k, 0)
    d_acq = k * req.count_nt

    pair = jnp.stack([d_rel, d_acq], axis=-1)
    pn = state.pn.at[rows, node_slot].add(pair)

    result = ConcResult(
        admitted=k,
        released_nt=d_rel,
        inflight_nt=inflight + d_acq,
        own_acquired_nt=own_taken + d_acq,
        own_released_nt=own_added + d_rel,
        clamped_nt=want_rel - d_rel,
    )
    return LimiterState(pn=pn, elapsed=state.elapsed), result


def _mutant_quota_admit_leaf_only(state, req, node_slot):
    """quota_take_batch admitting against the leaf headroom only: a
    tenant's users collectively overrun the tenant/global budgets."""
    from patrol_tpu.ops.hierquota import QuotaResult

    rows = jnp.concatenate([req.rows_global, req.rows_tenant, req.rows_user])
    pn_rows = state.pn[rows]
    spend = pn_rows[:, :, TAKEN].sum(axis=-1)
    k_batch = req.rows_user.shape[0]
    spend_g = spend[:k_batch]
    spend_t = spend[k_batch : 2 * k_batch]
    spend_u = spend[2 * k_batch :]

    head_g = req.limit_global_nt - spend_g
    head_t = req.limit_tenant_nt - spend_t
    head_u = req.limit_user_nt - spend_u
    head_min = head_u  # SEEDED defect: ancestors not consulted

    safe_count = jnp.where(req.count_nt <= 0, 1, req.count_nt)
    k = jnp.clip(head_min // safe_count, 0, req.nreq)
    k = jnp.where(req.count_nt > 0, k, 0)
    d = k * req.count_nt

    debit = jnp.concatenate([d, d, d])
    pn = state.pn.at[rows, node_slot, TAKEN].add(debit)

    result = QuotaResult(
        admitted=k,
        headroom_global_nt=head_g - d,
        headroom_tenant_nt=head_t - d,
        headroom_user_nt=head_u - d,
        own_taken_user_nt=pn_rows[2 * k_batch :, node_slot, TAKEN] + d,
    )
    return LimiterState(pn=pn, elapsed=state.elapsed), result


def _mutant_take_n_uncapped(state, packed, node_slot):
    """take_n_batch with the crowd-size clip dropped: the greedy grant
    admits ``have // count`` takes even past the ``nreq`` tickets
    actually waiting (and padding rows with ``nreq == 0`` start
    committing) — the coalesced row no longer replays the sequential
    per-ticket outcomes."""
    from patrol_tpu.ops.take import take_n_batch

    lifted = packed.at[5].set(jnp.int64(1) << 40)  # SEEDED defect
    return take_n_batch(state, lifted, node_slot)


def _mutant_split_grant_lifo(have_nt, admitted, count_nt, nreq):
    """split_grant admitting the LAST k tickets instead of the first:
    late arrivals jump the crowd — the aggregate grant is unchanged,
    but the FIFO fan-out order the tickets were promised is broken."""
    from patrol_tpu.ops.take import remaining_for_request

    return [
        remaining_for_request(have_nt, admitted, count_nt, nreq - 1 - i)
        for i in range(nreq)  # SEEDED defect: arrival order reversed
    ]


def _mutant_split_deny_charges(have_nt, admitted, count_nt, nreq):
    """split_grant charging denied tickets as if they had committed: a
    deny storm walks the REPORTED balance down a ledger nobody spent
    (admission itself is untouched — only the observable remaining
    drifts, the drift a replayed hot-key flood would amplify)."""
    from patrol_tpu.models.limiter import NANO

    out = []
    for i in range(nreq):
        ok = i < admitted
        remaining_nt = have_nt - (i + 1) * count_nt  # SEEDED defect
        out.append((max(remaining_nt, 0) // NANO, ok))
    return out


# ---------------------------------------------------------------------------
# The families.


KERNEL_FAMILIES: Tuple[KernelFamily, ...] = (
    KernelFamily(
        name="merge-join",
        domain="per-lane max join over the shared PN planes (the CvRDT "
        "merge every replication path reduces to)",
        prove_roots=(
            ProveRoot(
                "ops.merge.merge_batch", "patrol_tpu.ops.merge",
                "merge_batch", _ALL, structural="join",
                model="join_batch:merge_batch", tracer=_trace_merge_batch,
            ),
            ProveRoot(
                "ops.merge.merge_batch_folded", "patrol_tpu.ops.merge",
                "merge_batch_folded", _ALL, structural="join",
                model="join_batch:folded", tracer=_trace_merge_batch_folded,
            ),
            ProveRoot(
                "ops.merge.merge_rows_dense", "patrol_tpu.ops.merge",
                "merge_rows_dense", _ALL, structural="join",
                model="join_batch:rows_dense", tracer=_trace_merge_rows_dense,
            ),
            ProveRoot(
                "ops.commit.commit_blocks", "patrol_tpu.ops.commit",
                "commit_blocks", _ALL, structural="join",
                model="join_batch:commit_blocks", tracer=_trace_commit_blocks,
            ),
            ProveRoot(
                "ops.merge.merge_dense", "patrol_tpu.ops.merge",
                "merge_dense", _ALL, structural="join", model="dense_join",
                tracer=_trace_merge_dense,
            ),
            ProveRoot(
                # The mesh converge tree (pod-scale serving): the pure
                # butterfly-schedule twin of topology._tree_allreduce_max,
                # model-checked for flat-vs-tree equivalence, leaf-
                # permutation/duplication freedom, and monotonicity across
                # power-of-two AND ragged fan-ins — the laws that make a
                # hierarchical reduction path (Tascade, arXiv:2311.15810)
                # bit-exact for CRDT joins (arXiv:1410.2803).
                "parallel.topology.tree_reduce_states",
                "patrol_tpu.parallel.topology", "tree_reduce_states", _ALL,
                structural="join", model="tree_converge",
                tracer=_trace_tree_converge,
            ),
            ProveRoot(
                "ops.merge.read_rows", "patrol_tpu.ops.merge", "read_rows",
                ("PTP001", "PTP005"), structural="join",
                tracer=_trace_read_rows,
            ),
        ),
        absent={
            "ops.merge.read_rows:PTP002": (
                "pure gather: no algebra to replay — bit-exactness is "
                "covered by the engines' own read-back differentials"
            ),
            "ops.merge.read_rows:PTP003": (
                "a read commits nothing; there is no inverse to be exact "
                "against"
            ),
            "ops.merge.read_rows:PTP004": (
                "reads don't move the lattice; monotonicity is vacuous"
            ),
        },
        lin_exempt=(
            "joins are the replication substrate the lin model itself "
            "applies between events; ops.take.take_batch's spec covers "
            "the admission-facing surface"
        ),
        protocol="bucket-full",
        abi=(
            AbiObligation(
                "native.pt_fold_hybrid", "pt_fold_hybrid",
                ("PTA001", "PTA002", "PTA003"), "fold_conformance",
                twins=(
                    "ops.merge.merge_batch",
                    "ops.merge.merge_batch_folded",
                    "ops.merge.merge_rows_dense",
                ),
            ),
        ),
        bench_fields=("ingest_commit_equivalence",),
        mutations=(
            CertMutation(
                "merge-sums-instead-of-maxes", "protocol",
                "merge-sums-instead-of-maxes", "PTC001",
                note="join degenerates to a counter sum; replayed "
                "deliveries double-count",
            ),
            CertMutation(
                "merge-assigns-lww", "protocol", "merge-assigns-lww",
                "PTC002",
                note="last-writer-wins assignment loses concurrent lanes",
            ),
            CertMutation(
                "resync-overwrites-instead-of-joins", "protocol",
                "resync-overwrites-instead-of-joins", "PTC002",
                note="anti-entropy that overwrites forks the replicas it "
                "was meant to heal",
            ),
        ),
    ),
    KernelFamily(
        name="scalar-merge",
        domain="lossy scalar deficit attribution against reference peers "
        "(documented non-CRDT: PTP002/PTP003 deliberately absent)",
        prove_roots=(
            ProveRoot(
                "ops.merge.merge_scalar_batch", "patrol_tpu.ops.merge",
                "merge_scalar_batch", ("PTP001", "PTP004", "PTP005"),
                structural="callbacks", model="scalar_monotone",
                tracer=_trace_scalar_batch,
            ),
        ),
        absent={
            "ops.merge.merge_scalar_batch:PTP002": (
                "deficit attribution against reference peers is documented "
                "as lossy (kernel docstring): declaring only PTP004 "
                "records that design decision machine-checkably"
            ),
            "ops.merge.merge_scalar_batch:PTP003": (
                "no inverse exists for a lossy attribution; exactness is "
                "not claimed anywhere it could be relied on"
            ),
        },
        lin_exempt=(
            "the scalar plane is advisory (observability), never an "
            "admission input; no grants to linearize"
        ),
        protocol_exempt=(
            "not a replicated lattice: scalar deficits ride inside v1 "
            "datagrams and are re-derived, not joined"
        ),
        bench_exempt=(
            "no standalone device leg: the scalar fold runs fused inside "
            "the merge paths the merge-join family benches"
        ),
        mutations_exempt=(
            "documented-lossy family with a single monotone law; the "
            "scalar_monotone model's internal self-test already flips it"
        ),
    ),
    KernelFamily(
        name="bucket",
        domain="token bucket: greedy admission against the summed PN "
        "view, refill arithmetic in nanotokens",
        prove_roots=(
            ProveRoot(
                "ops.take.take_batch", "patrol_tpu.ops.take", "take_batch",
                ("PTP001", "PTP004", "PTP005"), structural="callbacks",
                model="take_monotone", tracer=_trace_take_batch,
            ),
            ProveRoot(
                # The hot-key take-n serving kernel (one dispatch per
                # coalesced crowd): the full obligation set, with the
                # algebraic codes mapped onto the coalescing laws by
                # the ``take_n_laws`` model — PTP002: one row carrying
                # nreq=n commits and admits EXACTLY what n sequential
                # unit takes at the same timestamp do (the replay leg
                # runs the certified per-ticket kernel, so a defect
                # cannot vouch for itself); PTP003: a fully denied row
                # is a state fixpoint (deny storms never drift the
                # bucket); PTP004: monotone lanes + own-lane locality.
                "ops.take.take_n_batch", "patrol_tpu.ops.take",
                "take_n_batch", _ALL, structural="callbacks",
                model="take_n_laws", tracer=_trace_take_n_batch,
            ),
            ProveRoot(
                # The host-side grant split behind take-n coalescing:
                # pure-Python fan-out of one coalesced row's grant to
                # its FIFO ticket queue. Registered as its own root so
                # the split ORDER is a certified law, not a convention:
                # PTP002 pins first-k-of-m against the sequential
                # ledger (LIFO / round-robin splits are rejected),
                # PTP003 pins the deny-storm balance.
                "ops.take.split_grant", "patrol_tpu.ops.take",
                "split_grant", ("PTP002", "PTP003"),
                model="take_split_fifo",
            ),
            ProveRoot(
                "ops.rate", "patrol_tpu.ops.rate", "parse_rate",
                ("PTP003", "PTP004"), model="rate_algebra",
            ),
            ProveRoot(
                "ops.wire.codec", "patrol_tpu.ops.wire", "encode",
                ("PTP003",), model="wire_roundtrip",
            ),
        ),
        absent={
            "ops.take.take_batch:PTP002": (
                "admission is order-sensitive by design (greedy grants); "
                "the commutative core is the join it scatters through, "
                "certified in merge-join"
            ),
            "ops.take.take_batch:PTP003": (
                "grants are not invertible — the forfeit clamp "
                "deliberately discards over-capacity remainder"
            ),
            "ops.take.split_grant:PTP001": (
                "host-side python fan-out: no jaxpr, nothing to trace"
            ),
            "ops.take.split_grant:PTP004": (
                "the split moves no lattice state — it fans one already-"
                "committed row's grant out to tickets; monotonicity "
                "lives in the take-n kernel root it serves"
            ),
            "ops.take.split_grant:PTP005": (
                "host-side python fan-out: no jaxpr, nothing to trace"
            ),
            "ops.rate:PTP001": (
                "host-side python parser: no jaxpr, nothing to trace"
            ),
            "ops.rate:PTP002": (
                "rate parsing has no join; PTP003 canonical-form "
                "round-trip plus PTP004 ordering are the whole algebra"
            ),
            "ops.rate:PTP005": (
                "host-side python parser: no jaxpr, nothing to trace"
            ),
            **_codec_absent("ops.wire.codec"),
        },
        lin_specs=(
            LinSpecFamily(
                "ops.take.take_batch", "patrol_tpu.ops.take", "take_batch",
                wire="full",
                note="classic take: v1 full-state broadcast, admission "
                "from the full local view with the over-capacity forfeit "
                "clamp",
            ),
            LinSpecFamily(
                "ops.take.take_n_batch", "patrol_tpu.ops.take",
                "take_n_batch", wire="full",
                note="hot-key coalesced take-n: the SAME sequential "
                "bucket spec — one row carrying nreq=n must hand out "
                "exactly the outcomes of n serialized takes, so "
                "coalescing is invisible to linearizability",
            ),
        ),
        protocol="bucket-full",
        abi=(
            AbiObligation(
                "native.pt_rx_classify", "pt_rx_classify",
                ("PTA001", "PTA002", "PTA003"), "classify_conformance",
                twins=("ops.wire.codec",),
            ),
            AbiObligation(
                "native.hls_schedules", "pt_hls_take_probe", ("PTA004",),
                "hls_interleavings",
            ),
        ),
        wire_codec="ops.wire.codec",
        bench_fields=(
            "device_kernel_breakdown",
            "take_coalesce_ratio",
            "hotkey_takes_per_s",
        ),
        mutations=(
            CertMutation(
                "take-n-uncapped-crowd", "prove",
                "ops.take.take_n_batch", "PTP002",
                note="crowd-size clip dropped: one coalesced row "
                "admits past its waiting tickets, diverging from the "
                "sequential per-ticket replay",
                mutant=_mutant_take_n_uncapped,
            ),
            CertMutation(
                "take-split-lifo", "prove",
                "ops.take.split_grant", "PTP002",
                note="grant split admits the LAST k tickets: late "
                "arrivals jump the FIFO crowd",
                mutant=_mutant_split_grant_lifo,
            ),
            CertMutation(
                "take-split-deny-drift", "prove",
                "ops.take.split_grant", "PTP003",
                note="denied tickets charged as if committed: the "
                "reported balance drifts under a deny storm",
                mutant=_mutant_split_deny_charges,
            ),
            CertMutation(
                "take-ignores-remote-lanes", "protocol",
                "take-ignores-remote-lanes", "PTC003",
                note="own-lane-only admission view breaks the AP "
                "overspend bound",
            ),
            CertMutation(
                "incast-gate-bypass", "protocol", "incast-gate-bypass",
                "PTC003",
                note="the incast admission gate is part of the bucket's "
                "bound; bypassing it over-admits under fan-in",
            ),
            CertMutation(
                "take-ignores-visible-remote-spend", "lin",
                "take-ignores-visible-remote-spend", "PTN001",
                note="delivered remote lanes excluded from the admission "
                "view",
            ),
            CertMutation(
                "grant-exceeds-spec-on-sync-schedule", "lin",
                "grant-exceeds-spec-on-sync-schedule", "PTN003",
                note="over-grant on a fully synchronous schedule",
            ),
            CertMutation(
                "visibility-violating-linearization-accepted", "lin",
                "visibility-violating-linearization-accepted", "PTN002",
                note="checker soundness: an illegal witness order must "
                "not be accepted",
            ),
        ),
    ),
    KernelFamily(
        name="delta",
        domain="wire-v2 absolute own-lane intervals: delta-fold ingest, "
        "device-resident raw decode, watermark visibility",
        prove_roots=(
            ProveRoot(
                "ops.delta.delta_fold", "patrol_tpu.ops.delta",
                "delta_fold", _ALL, structural="join",
                model="join_batch:delta_fold", tracer=_trace_delta_fold,
            ),
            ProveRoot(
                # Device-resident ingest (r15): raw dv2 datagram byte
                # planes → framing walk + entry extraction + checksum/
                # validation verdicts + sentinel padding + scatter-max
                # fold, ONE dispatch. The ``raw_ingest`` model checks it
                # against the python wire decoder + reference join over
                # real datagram bytes: packet-order commutativity,
                # duplicated-plane idempotence, monotonicity, and strict
                # all-or-nothing corruption rejection (every truncation/
                # flip verdict must match decode_delta_packet, and
                # rejected planes must merge NOTHING). PTP001 runs the
                # join allowlist on the state planes — the decode
                # arithmetic touches only untainted plane bytes, so the
                # fold leg must stay pure scatter-max; PTP005 pins the
                # state dtypes/shapes.
                "ops.ingest.decode_fold_raw", "patrol_tpu.ops.ingest",
                "decode_fold_raw", _ALL, structural="join",
                model="raw_ingest", tracer=_trace_decode_fold_raw,
            ),
            ProveRoot(
                "ops.wire.delta_codec", "patrol_tpu.ops.wire",
                "encode_delta_packet", ("PTP003",), model="delta_roundtrip",
            ),
        ),
        absent=_codec_absent("ops.wire.delta_codec"),
        lin_specs=(
            LinSpecFamily(
                "ops.delta.delta_fold", "patrol_tpu.ops.delta",
                "delta_fold", wire="delta",
                note="delta-fold ingest: wire-v2 absolute own-lane "
                "intervals, visibility carried by the folded watermarks",
            ),
        ),
        protocol="bucket-delta",
        abi=(
            AbiObligation(
                # Zero-copy rx ring (device-resident ingest): every
                # interleaving of lease (rx thread) vs commit (engine
                # completer — "the pump" of the plane hand-off) against a
                # lowest-free-first model, plus the double-commit / stray-
                # index refusals that guard the use-after-recycle class.
                "native.rx_ring_schedules", "pt_rx_ring_lease", ("PTA004",),
                "rxring_interleavings",
            ),
            AbiObligation(
                # The rx thread's dv2 structure walk: the ctypes argument
                # types against the C definition, a binding that releases
                # the interpreter lock, every output written within its
                # declared size, and every RawWalk field bit-exact against
                # the numpy reference ops.ingest.host_walk.
                "native.pt_raw_walk", "pt_raw_walk", ("PTA001",),
                "raw_walk_conformance",
            ),
        ),
        wire_codec="ops.wire.delta_codec",
        bench_fields=("ingest_raw_smoke_deltas",),
        mutations=(
            CertMutation(
                "delta-ships-increments-not-absolutes", "protocol",
                "delta-ships-increments-not-absolutes", "PTC001",
                note="increments on the wire double-apply under redelivery",
            ),
            CertMutation(
                "delta-gc-before-ack", "protocol", "delta-gc-before-ack",
                "PTC001",
                note="eager delta GC drops intervals a slow peer never saw",
            ),
        ),
    ),
    KernelFamily(
        name="lifecycle",
        domain="idle-bucket GC: the IsZero reclaim predicate and "
        "tombstoned own-lane re-creation",
        prove_roots=(
            ProveRoot(
                # The bucket-lifecycle IsZero predicate (idle-bucket GC,
                # ROADMAP item 4): full obligation set, with the algebraic
                # codes mapped onto the GC conservation laws by the
                # ``lifecycle_iszero`` model (analysis/prove.py) —
                # PTP002: a "full" verdict is *sound* (reclaim-then-
                # recreate is take-observation-equivalent to the original
                # row, bit-exact against the take kernel — the admitted-
                # token conservation law); PTP003: reclaim re-entry is
                # exact (zero lanes are the join's bottom, so
                # join(fresh, old) == old); PTP004: the verdict is
                # monotone in time (a missed sweep window can only delay
                # a reclaim, never invalidate it). PTP001/PTP005 run
                # structurally: no callbacks, and NO state outputs at all
                # — the predicate is a pure read.
                "ops.lifecycle.lifecycle_probe", "patrol_tpu.ops.lifecycle",
                "lifecycle_probe", _ALL, structural="callbacks",
                model="lifecycle_iszero", tracer=_trace_lifecycle_probe,
            ),
        ),
        lin_specs=(
            LinSpecFamily(
                "ops.lifecycle.lifecycle_probe", "patrol_tpu.ops.lifecycle",
                "lifecycle_probe", wire="full", lifecycle=True,
                note="lifecycle GC re-creation: IsZero reclaim with the "
                "tombstoned own lane, refills in the schedule alphabet",
            ),
        ),
        protocol="lifecycle-gc",
        bench_fields=("mesh_gc_reclaimed_probe",),
        mutations=(
            CertMutation(
                "gc-drops-admitted-tokens", "protocol",
                "gc-drops-admitted-tokens", "PTC006",
                note="reclaiming a non-zero row un-spends admitted tokens",
            ),
            CertMutation(
                "gc-treats-collected-as-unknown", "protocol",
                "gc-treats-collected-as-unknown", "PTC001",
                note="a tombstone read back as bottom resurrects "
                "collected spend",
            ),
            CertMutation(
                "gc-forgets-visible-admits", "lin",
                "gc-forgets-visible-admits", "PTN004",
                note="reclaim erases grants the visibility ledger still "
                "carries",
            ),
        ),
    ),
    KernelFamily(
        name="gcra",
        domain="GCRA / sliding window: the Theoretical Arrival Time as a "
        "per-lane max register, conformance iff TAT <= now + tol",
        prove_roots=(
            ProveRoot(
                "ops.gcra.gcra_take_batch", "patrol_tpu.ops.gcra",
                "gcra_take_batch", ("PTP001", "PTP002", "PTP004", "PTP005"),
                structural="callbacks", model="gcra_laws",
                tracer=_trace_gcra_take,
            ),
            ProveRoot(
                "ops.wire.gcra_trailer", "patrol_tpu.ops.wire",
                "encode_gcra_trailer", ("PTP003",),
                model="cert_trailer_roundtrip",
            ),
        ),
        absent={
            "ops.gcra.gcra_take_batch:PTP003": (
                "admission is not invertible (a conforming grant advances "
                "the TAT permanently); exactness lives in the trailer "
                "codec root's PTP003"
            ),
            **_codec_absent("ops.wire.gcra_trailer"),
        },
        lin_specs=(
            LinSpecFamily(
                "ops.gcra.gcra_take_batch", "patrol_tpu.ops.gcra",
                "gcra_take_batch", wire="delta", algebra="gcra",
                note="TAT max register: per-partition-side sequential "
                "GCRA replay (SequentialGcra) over the protocol-model "
                "cluster, shared injected clock in the alphabet",
            ),
        ),
        protocol="gcra",
        wire_codec="ops.wire.gcra_trailer",
        bench_fields=("cert_gcra_admitted",),
        mutations=(
            CertMutation(
                "gcra-window-off-by-one", "prove",
                "ops.gcra.gcra_take_batch", "PTP002",
                note="conformance window widened by one emission "
                "interval: burst+1 admitted",
                mutant=_mutant_gcra_window_off_by_one,
            ),
            CertMutation(
                "gcra-conformance-own-lane-only", "protocol", "gcra",
                "PTC006",
                note="judging conformance from the own TAT lane ignores "
                "merged remote watermarks: overspend past the AP bound",
                laws=GcraLaws(view="own"),
            ),
        ),
    ),
    KernelFamily(
        name="concurrency",
        domain="in-flight concurrency limit: paired PN lanes (TAKEN = "
        "acquires, ADDED = releases), inflight = sum difference",
        prove_roots=(
            ProveRoot(
                "ops.concurrency.conc_acquire_batch",
                "patrol_tpu.ops.concurrency", "conc_acquire_batch",
                ("PTP001", "PTP002", "PTP004", "PTP005"),
                structural="callbacks", model="conc_laws",
                tracer=_trace_conc_acquire,
            ),
            ProveRoot(
                "ops.wire.conc_trailer", "patrol_tpu.ops.wire",
                "encode_conc_trailer", ("PTP003",),
                model="cert_trailer_roundtrip",
            ),
        ),
        absent={
            "ops.concurrency.conc_acquire_batch:PTP003": (
                "acquire/release ticks are not invertible on monotone "
                "lanes (that is the point of the clamp); exactness lives "
                "in the trailer codec root's PTP003"
            ),
            **_codec_absent("ops.wire.conc_trailer"),
        },
        lin_specs=(
            LinSpecFamily(
                "ops.concurrency.conc_acquire_batch",
                "patrol_tpu.ops.concurrency", "conc_acquire_batch",
                wire="delta", algebra="conc",
                note="client-owned leases: per-side sequential replay "
                "(SequentialConc) — the own-lane release clamp IS lease "
                "ownership in the sequential limit",
            ),
        ),
        protocol="concurrency",
        wire_codec="ops.wire.conc_trailer",
        bench_fields=("cert_conc_admitted",),
        mutations=(
            CertMutation(
                "conc-release-unclamped", "prove",
                "ops.concurrency.conc_acquire_batch", "PTP002",
                note="phantom release: ADDED lane driven past TAKEN, "
                "capacity returned that was never held",
                mutant=_mutant_conc_release_unclamped,
            ),
            CertMutation(
                "conc-phantom-release-model", "protocol", "concurrency",
                "PTC006",
                note="the model twin of the clamp: uncapped releases "
                "break held <= limit x sides",
                laws=ConcLaws(release="uncapped"),
            ),
        ),
    ),
    KernelFamily(
        name="hierquota",
        domain="hierarchical quotas global→tenant→user: path-minimum "
        "admission, all-or-nothing three-level debit in one scatter",
        prove_roots=(
            ProveRoot(
                "ops.hierquota.quota_take_batch",
                "patrol_tpu.ops.hierquota", "quota_take_batch",
                ("PTP001", "PTP002", "PTP004", "PTP005"),
                structural="callbacks", model="quota_laws",
                tracer=_trace_quota_take,
            ),
            ProveRoot(
                "ops.wire.quota_trailer", "patrol_tpu.ops.wire",
                "encode_quota_trailer", ("PTP003",),
                model="cert_trailer_roundtrip",
            ),
        ),
        absent={
            "ops.hierquota.quota_take_batch:PTP003": (
                "debits are permanent on monotone G-counter lanes; "
                "exactness lives in the trailer codec root's PTP003"
            ),
            **_codec_absent("ops.wire.quota_trailer"),
        },
        lin_specs=(
            LinSpecFamily(
                "ops.hierquota.quota_take_batch",
                "patrol_tpu.ops.hierquota", "quota_take_batch",
                wire="delta", algebra="quota",
                note="path-minimum admission: per-side sequential replay "
                "(SequentialQuota) against the three-level model cluster",
            ),
        ),
        protocol="hierquota",
        wire_codec="ops.wire.quota_trailer",
        bench_fields=("cert_quota_admitted",),
        mutations=(
            CertMutation(
                "quota-admit-leaf-only", "prove",
                "ops.hierquota.quota_take_batch", "PTP002",
                note="leaf-only headroom: users collectively overrun the "
                "tenant/global pools",
                mutant=_mutant_quota_admit_leaf_only,
            ),
            CertMutation(
                "quota-debit-leaf-only", "protocol", "hierquota", "PTC006",
                note="the model twin: leaf-only debits break per-level "
                "conservation whenever an ancestor limit is tighter",
                laws=QuotaLaws(debit="leaf-only"),
            ),
        ),
    ),
)


# ---------------------------------------------------------------------------
# Toolchain-wide ABI obligations that belong to no single lattice family
# (the effects-table sweep covers every exported native symbol).
TOOLCHAIN_ABI: Tuple[AbiObligation, ...] = (
    AbiObligation(
        # The HTTP front's answer hand-back keeps the interpreter lock
        # (NATIVE_EFFECTS keeps_gil): every binding as the table declares
        # it, the argument types against the C definition, leaf mutexes
        # only, no native call into Python, and a live answer.
        "native.pt_http_answer_takes", "pt_http_answer_takes",
        ("PTA001",), "held_binding_conformance",
    ),
    AbiObligation(
        "native.effects_table", None, ("PTA005",), "effects_table",
    ),
)


# ---------------------------------------------------------------------------
# Derived flat registries — the historical exports; every stage driver
# and test keeps consuming these names unchanged. Order follows the
# family declaration order above.

PROVE_ROOTS: Tuple[ProveRoot, ...] = tuple(
    root for fam in KERNEL_FAMILIES for root in fam.prove_roots
)

LIN_SPECS: Tuple[LinSpecFamily, ...] = tuple(
    spec for fam in KERNEL_FAMILIES for spec in fam.lin_specs
)

ABI_OBLIGATIONS: Tuple[AbiObligation, ...] = (
    tuple(ob for fam in KERNEL_FAMILIES for ob in fam.abi) + TOOLCHAIN_ABI
)


# --- PTP006 (registration completeness): kernels the runtime engines
# dispatch through jit that are deliberately NOT in PROVE_ROOTS, each
# with the reason on record. analysis/prove.py sweeps the engine
# dispatch graph — and stage 9's PTK004 sweeps ops/ module-level
# ``*_jit`` bindings — and flags any jitted kernel found in neither
# registry: a new kernel can no longer land without declared
# obligations.
PROVE_EXEMPT: frozenset = frozenset(
    {
        # zero_rows writes constant zeros into selected rows — a pure
        # scatter of the lattice bottom with no algebra of its own. Its
        # lattice-facing laws are certified where they matter: the
        # lifecycle_iszero model proves reclaim-then-recreate (which IS
        # zero_rows + re-seed) take-observation-equivalent and join-
        # re-entry exact (PTP002/PTP003 on ops.lifecycle.lifecycle_probe).
        ("patrol_tpu.ops.merge", "zero_rows"),
    }
)


# ---------------------------------------------------------------------------
# Dispatch-discipline registry (check.sh stage 10, patrol-dispatch).
# Every kernel the runtime engines push through jax.jit declares HERE the
# dispatch contract stage 10 proves: which buffers are donated, which
# argnames are static, what shape-bucket law its call sites must pad to
# (StagingPool's power-of-two buckets, machine-readable at last), and
# which witness path re-drives it post-warmup under the compile counter
# and transfer guard (analysis/dispatch.py::WITNESS_PATHS). A kernel
# with no witness carries a written justification instead — PTD005
# rejects both a dispatched kernel missing from this registry and a
# registered kernel with neither witness nor justification.


@dataclasses.dataclass(frozen=True)
class DispatchSpec:
    """One jit-dispatched kernel's dispatch-discipline contract.

    ``buckets`` names the shape-bucket law of the kernel's call sites:

    * ``"pow2"`` — batches are padded through ``engine._pad_size`` with
      the declared ``(bucket_lo, bucket_hi)`` clamp; PTD001 requires a
      textually matching ``_pad_size`` site in the engine files (lo/hi
      compared by ``ast.unparse``, defaults ``"8"`` /
      ``"MAX_MERGE_ROWS"``), so silently dropping the padding — or
      drifting the clamp away from the declared ceiling — is a finding.
    * ``"fixed"`` — every dispatch ships one pinned shape
      (``bucket_hi`` names the constant: the commit ring's
      ``MAX_MERGE_ROWS`` block width, the rx ring's plane geometry).
    * ``"caller"`` — shapes are the caller's contract (the cert-kit
      microbatches: bench/tests drive fixed shapes); the witness still
      pins post-warmup stability for the shapes it drives.

    ``witness`` names the ``analysis/dispatch.py::WITNESS_PATHS`` entry
    that warms and re-drives this kernel (PTD004); ``witness_absent``
    is the REQUIRED justification when no witness path can reach it.
    """

    name: str
    module: str  # owning ops module, e.g. "patrol_tpu.ops.take"
    attr: str  # kernel function name in that module
    donate_argnums: Tuple[int, ...] = (0,)
    static_argnames: Tuple[str, ...] = ()
    buckets: str = "pow2"  # "pow2" | "fixed" | "caller"
    bucket_lo: str = "8"
    bucket_hi: str = "MAX_MERGE_ROWS"
    witness: str = ""
    witness_absent: str = ""
    note: str = ""


DISPATCH_SPECS: Tuple[DispatchSpec, ...] = (
    DispatchSpec(
        "take_batch", "patrol_tpu.ops.take", "take_batch",
        static_argnames=("node_slot",),
        bucket_hi="MAX_TAKE_ROWS", witness="take",
        note="packed [8,K] request / [7,K] result; feeder tick path",
    ),
    DispatchSpec(
        "take_n_batch", "patrol_tpu.ops.take", "take_n_batch",
        static_argnames=("node_slot",),
        bucket_hi="MAX_TAKE_ROWS", witness="take_n",
        note="the coalesced serving wrapper the feeder tick actually "
        "dispatches: packed [8,K] in / [7,K] out with hot-key crowds "
        "folded into the nreq row",
    ),
    DispatchSpec(
        "merge_batch", "patrol_tpu.ops.merge", "merge_batch",
        witness="mesh_step",
        note="scatter-max join inside the fused mesh step "
        "(topology.cluster_step); the single-device tick folds first",
    ),
    DispatchSpec(
        "merge_batch_folded", "patrol_tpu.ops.merge", "merge_batch_folded",
        witness="merge_folded",
        note="unique/sorted-asserted fold; the merge tick's commit",
    ),
    DispatchSpec(
        "commit_blocks", "patrol_tpu.ops.commit", "commit_blocks",
        buckets="fixed", witness="commit_blocks",
        note="[6,J,MAX_MERGE_ROWS] coalesced block ring, J a pow2 "
        "block count warmed per variant",
    ),
    DispatchSpec(
        "merge_rows_dense", "patrol_tpu.ops.merge", "merge_rows_dense",
        bucket_hi="MAX_ROW_DENSE", witness="merge_rows_dense",
        note="row-window dense half of the fold-to-dense hybrid",
    ),
    DispatchSpec(
        "merge_scalar_batch", "patrol_tpu.ops.merge", "merge_scalar_batch",
        witness="merge_scalar",
        note="deficit-attribution interop merge",
    ),
    DispatchSpec(
        "zero_rows", "patrol_tpu.ops.merge", "zero_rows",
        bucket_hi="1 << 20", witness="zero_rows",
        note="lifecycle reclaim / checkpoint-restore scatter of bottom",
    ),
    DispatchSpec(
        "lifecycle_probe", "patrol_tpu.ops.lifecycle", "lifecycle_probe",
        donate_argnums=(), static_argnames=("node_slot",),
        bucket_hi="1 << 20", witness="lifecycle_probe",
        note="pure read (no donation): GC sweep idle/full probe",
    ),
    DispatchSpec(
        "gcra_take_batch", "patrol_tpu.ops.gcra", "gcra_take_batch",
        static_argnames=("node_slot",), buckets="caller", witness="gcra",
    ),
    DispatchSpec(
        "conc_acquire_batch", "patrol_tpu.ops.concurrency",
        "conc_acquire_batch",
        static_argnames=("node_slot",), buckets="caller", witness="conc",
    ),
    DispatchSpec(
        "quota_take_batch", "patrol_tpu.ops.hierquota", "quota_take_batch",
        static_argnames=("node_slot",), buckets="caller", witness="quota",
    ),
    DispatchSpec(
        "delta_fold", "patrol_tpu.ops.delta", "delta_fold",
        witness="delta_fold",
        note="interval-encoded replication deltas, host decode fold",
    ),
    DispatchSpec(
        "decode_fold_raw", "patrol_tpu.ops.ingest", "decode_fold_raw",
        buckets="fixed", bucket_hi="rx-ring planes",
        witness="raw_ingest",
        note="whole rx ring ships as-is: [P,row_w] planes + [P]/[P,E] "
        "framing, geometry pinned by the ring allocation",
    ),
    DispatchSpec(
        "read_rows", "patrol_tpu.ops.merge", "read_rows",
        donate_argnums=(), bucket_lo="1", bucket_hi="1 << 20",
        witness="read_rows",
        note="eager (un-jitted) padded gather behind every "
        "snapshot/introspection read; donation-free by construction",
    ),
)

DISPATCH_KERNELS: frozenset = frozenset(
    (s.module, s.attr) for s in DISPATCH_SPECS
)
