"""patrol-prove: a jaxpr-level CRDT invariant prover (stage 4 of patrol-check).

The convergence story of this repo rests on algebraic claims the kernels
only state in prose: ``ops/merge.py`` promises that every replica reaches
an identical state "regardless of delivery order, duplication, or loss"
because the joins are max-based. PR 2's ``patrol-check`` lints the Python
*sugar*; this module drops one level and checks the kernels **as traced**
— the jaxpr IR that actually reaches XLA — so a refactor that swaps a
``max`` for a ``+``, drops a signed clamp, or lets an f32 creep into the
pn planes fails the gate before it forks CRDT state cluster-wide.

Two static passes over every registered kernel root
(:data:`patrol_tpu.ops.obligations.PROVE_ROOTS`):

1. **Structural lattice check** (PTP001) — trace the kernel with
   ``jax.make_jaxpr`` over its declared abstract shapes, taint the CRDT
   state-plane inputs, and walk the IR. On a *join* root, a tainted value
   may only flow through join primitives (``max``, ``scatter-max``) and
   shape/layout-transparent ones (gather, slice, reshape, bitcast, …);
   any other primitive consuming a merged plane — ``add``, ``sub``,
   ``mul``, ``reduce_sum``, ``scatter-add`` — is a finding, as is a
   float cast on a nanotoken plane (PTL004 at the IR level, below the
   Python sugar) and any data-dependent callback/sync primitive. *Delta*
   roots (the take kernel's monotone adds on the local side) skip the
   join allowlist but keep the callback scan.

2. **Exhaustive small-domain model check** (PTP002-PTP004) — run the
   *same resolved callable* over every state/delta combination of a tiny
   lattice domain and confirm, bit-exactly, the properties the reference
   only samples with its 10k-permutation test (bucket_test.go:68-114):
   commutativity (PTP002), idempotence under duplication (PTP003), and
   merge/take monotonicity (PTP004). Enumerations are vmapped and run in
   one (chunked) device call per property.

PTP005 (dtype-stable under jit) re-traces the root and asserts the state
outputs keep the declared integer dtypes and shapes — the "f32 creeping
into the pn planes" failure class.

Obligation codes:

====== =======================================================
PTP001 join allowlist / callback-free jaxpr (structural pass)
PTP002 commutativity over the small lattice domain
PTP003 idempotence under duplication / round-trip stability
PTP004 monotonicity (join and take never shrink a plane)
PTP005 dtype- and shape-stability of the state planes under jit
PTP006 registration completeness: every jit-dispatched engine
       kernel is in PROVE_ROOTS or PROVE_EXEMPT (static sweep)
====== =======================================================

Findings reuse :class:`patrol_tpu.analysis.lint.Finding` and the same
inline suppression machinery (``# patrol-lint: disable=PTP001``); every
suppression is a greppable declaration, reviewed like code. Drivers:
``scripts/prove_repo.py`` (standalone / stage 4 of ``scripts/check.sh``)
and the ``pytest -m prove`` fixture self-tests in ``tests/test_prove.py``.
"""

from __future__ import annotations

import ast
import dataclasses
import importlib
import inspect
import itertools
import os
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from patrol_tpu.analysis.lint import Finding

__all__ = [
    "ProveRoot",
    "Trace",
    "prove_root",
    "prove_all",
    "prove_repo",
    "ALL_CODES",
]

# Per-root obligation codes. PTP006 (registration completeness) is a
# repo-level sweep over the engine dispatch graph, not a declarable
# per-root obligation, so it lives outside this tuple.
ALL_CODES = ("PTP001", "PTP002", "PTP003", "PTP004", "PTP005")

# ---------------------------------------------------------------------------
# Structural pass configuration. The allowlists live HERE, in code review's
# line of sight (same discipline as lint.py's CLOCK_SEAMS et al.).

# Primitives that JOIN two lattice values. The whole CRDT argument is that
# state planes are only ever combined through these.
JOIN_PRIMS = {"max", "scatter-max"}

# Primitives that move/reshape/select lattice values without combining
# them arithmetically — transparent to the join structure.
TRANSPARENT_PRIMS = {
    "broadcast_in_dim",
    "reshape",
    "squeeze",
    "expand_dims",
    "transpose",
    "rev",
    "slice",
    "dynamic_slice",
    "gather",
    "concatenate",
    "pad",
    "select_n",
    "convert_element_type",  # float targets are flagged separately
    "bitcast_convert_type",  # the u64-max reformulation in merge_dense
    "copy",
    "stop_gradient",
    "reduce_max",
    "reduce_or",
    "and",
    "or",
    "not",
    "eq",
    "ne",
    "lt",
    "le",
    "gt",
    "ge",  # comparisons yield bools, not planes; harmless to observe
}

# Call-like primitives whose sub-jaxpr maps invars/outvars 1:1 — recurse
# with the taint mapped through. JAX 0.9 names a nested jit "jit"; left
# unrecognized it fell to the conservative control-flow branch, which
# taints every operand — a gather's row indices included, so the inlined
# negative-index normalization (lt/add/select_n on the rows) read as
# arithmetic on a merged plane.
_CALL_PRIMS = {"jit", "closed_call", "core_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat"}

# Host round-trips / side channels: never allowed in a kernel root,
# regardless of profile. A data-dependent callback on the merge path is a
# per-tick host sync at best and a nondeterminism source at worst.
CALLBACK_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "callback",
    "host_callback_call",
    "outside_call",
    "infeed",
    "outfeed",
    "debug_print",
}

_FLOAT_KINDS = ("f",)  # np.dtype.kind for float/bfloat dtypes


def _is_float_dtype(dtype) -> bool:
    try:
        return np.dtype(dtype).kind in _FLOAT_KINDS
    except TypeError:
        # extended dtypes (bfloat16 etc.) expose .kind themselves
        return getattr(dtype, "kind", "") in ("f", "V") and "float" in str(dtype)


# ---------------------------------------------------------------------------
# Registry types. The registry itself (PROVE_ROOTS) lives next to the
# kernels in patrol_tpu/ops/obligations.py.


class Trace:
    """A traced root: its closed jaxpr plus which flat invars/outvars are
    CRDT state planes (the taint sources / dtype-stability targets)."""

    def __init__(
        self,
        closed_jaxpr,
        state_in: Sequence[int],
        state_out: Sequence[int],
        shapes_must_match: bool = True,
    ):
        self.closed_jaxpr = closed_jaxpr
        self.state_in = tuple(state_in)
        self.state_out = tuple(state_out)
        self.shapes_must_match = shapes_must_match


@dataclasses.dataclass(frozen=True)
class ProveRoot:
    """One registered kernel root and its declared obligations.

    ``module``/``attr`` are resolved dynamically at prove time (so a
    monkeypatched kernel — the mutation self-tests — is what gets
    checked). ``structural`` selects the PTP001 profile: ``"join"``
    (strict lattice allowlist) for CvRDT joins, ``"callbacks"`` for
    delta-side kernels whose local adds are legitimate, ``None`` for
    pure-Python roots. ``model`` is the pass-2 dispatch tag; ``tracer``
    builds the :class:`Trace` from the resolved callable."""

    name: str
    module: str
    attr: str
    obligations: Tuple[str, ...]
    structural: Optional[str] = None  # "join" | "callbacks" | None
    model: Optional[str] = None
    tracer: Optional[Callable[[Callable], Trace]] = None

    def resolve(self) -> Callable:
        return getattr(importlib.import_module(self.module), self.attr)


# ---------------------------------------------------------------------------
# Finding sites: prefer the jaxpr equation's own source line (jax keeps a
# user-frame traceback per eqn); fall back to the kernel's def line.


def _relpath(path: str) -> str:
    """Absolute → repo-relative ("patrol_tpu/..."), best-effort."""
    norm = path.replace(os.sep, "/")
    marker = "/patrol_tpu/"
    if marker in norm:
        return "patrol_tpu/" + norm.split(marker, 1)[1]
    return norm


def _def_site(fn: Callable, root: ProveRoot) -> Tuple[str, int]:
    try:
        path = inspect.getsourcefile(fn) or ""
        _, line = inspect.getsourcelines(fn)
        return _relpath(path), line
    except (TypeError, OSError):
        return _relpath(root.module.replace(".", "/") + ".py"), 1


def _eqn_site(eqn, default: Tuple[str, int]) -> Tuple[str, int]:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None and frame.file_name:
            return _relpath(frame.file_name), int(frame.start_line)
    except Exception:
        pass
    return default


# ---------------------------------------------------------------------------
# Pass 1 — structural lattice check over the jaxpr.


def _subjaxprs(eqn):
    """(param_name, ClosedJaxpr-or-Jaxpr) pairs inside an equation."""
    out = []
    for k, v in eqn.params.items():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                out.append((k, item.jaxpr))  # ClosedJaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                out.append((k, item))  # raw Jaxpr
    return out


def structural_check(root: ProveRoot, trace: Trace, site: Tuple[str, int]) -> List[Finding]:
    """PTP001: walk the jaxpr; on 'join' roots enforce the lattice
    allowlist on every value tainted by a state plane; on every root
    reject callback/sync primitives."""
    findings: List[Finding] = []
    join = root.structural == "join"

    def emit(eqn, msg: str) -> None:
        path, line = _eqn_site(eqn, site)
        findings.append(Finding("PTP001", path, line, f"[{root.name}] {msg}"))

    def is_var(v) -> bool:
        return hasattr(v, "aval") and not type(v).__name__ == "Literal"

    def walk(jaxpr, tainted_invars: set) -> set:
        """→ set of tainted outvars (by object identity)."""
        tainted = set(tainted_invars)
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim in CALLBACK_PRIMS:
                emit(
                    eqn,
                    f"data-dependent callback/sync primitive '{prim}' in a "
                    "kernel root: every engine tick would round-trip the host",
                )
            hot = [v for v in eqn.invars if is_var(v) and v in tainted]

            # Recurse into call-like primitives with the taint mapped 1:1.
            if prim in _CALL_PRIMS:
                subs = _subjaxprs(eqn)
                if subs:
                    _, sub = subs[0]
                    sub_taint = {
                        sv
                        for v, sv in zip(eqn.invars, sub.invars)
                        if is_var(v) and v in tainted
                    }
                    sub_out = walk(sub, sub_taint)
                    for v, sv in zip(eqn.outvars, sub.outvars):
                        if is_var(sv) and sv in sub_out:
                            tainted.add(v)
                    continue

            # Control flow (scan/while/cond): conservative — taint the whole
            # body and analyze it; a loop combining state planes should be
            # looked at by a human either way.
            subs = _subjaxprs(eqn)
            if subs:
                if hot:
                    for _, sub in subs:
                        walk(sub, set(sub.invars))
                    tainted.update(eqn.outvars)
                continue

            if not hot:
                continue
            if not join:
                continue

            if prim in JOIN_PRIMS:
                tainted.update(eqn.outvars)
            elif prim == "convert_element_type" and _is_float_dtype(
                eqn.params.get("new_dtype")
            ):
                emit(
                    eqn,
                    f"float cast ({eqn.params.get('new_dtype')}) on a "
                    "nanotoken state plane: bit-determinism across replicas "
                    "is lost (PTL004 at the IR level)",
                )
                tainted.update(eqn.outvars)
            elif prim in TRANSPARENT_PRIMS:
                tainted.update(eqn.outvars)
            else:
                emit(
                    eqn,
                    f"primitive '{prim}' outside the join allowlist consumes "
                    "a merged CRDT state plane; joins must stay max-based "
                    "(commutative/associative/idempotent) or convergence "
                    "breaks under reordering/duplication",
                )
                tainted.update(eqn.outvars)
        return {v for v in jaxpr.outvars if is_var(v) and v in tainted}

    jaxpr = trace.closed_jaxpr.jaxpr
    taint = {jaxpr.invars[i] for i in trace.state_in}
    walk(jaxpr, taint)
    return findings


# ---------------------------------------------------------------------------
# PTP005 — dtype/shape stability of the state planes under jit.


def dtype_stability_check(
    root: ProveRoot, trace: Trace, site: Tuple[str, int]
) -> List[Finding]:
    findings: List[Finding] = []
    in_avals = [trace.closed_jaxpr.in_avals[i] for i in trace.state_in]
    out_avals = [trace.closed_jaxpr.out_avals[i] for i in trace.state_out]
    for i, out in enumerate(out_avals):
        ref = in_avals[i] if i < len(in_avals) else in_avals[-1]
        if _is_float_dtype(out.dtype):
            findings.append(
                Finding(
                    "PTP005",
                    site[0],
                    site[1],
                    f"[{root.name}] state output {i} has float dtype "
                    f"{out.dtype}: nanotoken planes must stay integral for "
                    "bit-deterministic convergence",
                )
            )
        elif out.dtype != ref.dtype:
            findings.append(
                Finding(
                    "PTP005",
                    site[0],
                    site[1],
                    f"[{root.name}] state output {i} dtype {out.dtype} != "
                    f"declared plane dtype {ref.dtype} (unstable under jit / "
                    "x64 mode changes)",
                )
            )
        if trace.shapes_must_match and tuple(out.shape) != tuple(ref.shape):
            findings.append(
                Finding(
                    "PTP005",
                    site[0],
                    site[1],
                    f"[{root.name}] state output {i} shape {tuple(out.shape)} "
                    f"!= input plane shape {tuple(ref.shape)}",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# Pass 2 — exhaustive small-domain model checking. All enumerations are
# vmapped; a property over N cases is one (chunked) call, not N.

_CHUNK = 65536


def _chunked(app: Callable, arrays: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Apply a vmapped callable over the leading axis in bounded chunks."""
    n = len(arrays[0])
    outs: List[List[np.ndarray]] = []
    for lo in range(0, n, _CHUNK):
        res = app(*[a[lo : lo + _CHUNK] for a in arrays])
        if not isinstance(res, (tuple, list)):
            res = (res,)
        outs.append([np.asarray(r) for r in res])
    return [np.concatenate([c[i] for c in outs]) for i in range(len(outs[0]))]


def _grid(*groups: Sequence[np.ndarray]) -> List[np.ndarray]:
    """Cross product over *groups* of co-indexed arrays: each group is a
    tuple of arrays sharing a leading axis (e.g. a state's (pn, elapsed));
    the group's arrays stay paired while groups cross with each other.
    → the flattened per-array views, one per input array, in order."""
    sizes = [len(g[0]) for g in groups]
    idx = np.meshgrid(*[np.arange(s) for s in sizes], indexing="ij")
    idx = [i.reshape(-1) for i in idx]
    out: List[np.ndarray] = []
    for g, i in zip(groups, idx):
        out.extend(a[i] for a in g)
    return out


def _first_bad(eq_mask: np.ndarray) -> Optional[int]:
    bad = np.flatnonzero(~eq_mask)
    return int(bad[0]) if len(bad) else None


def _states_eq(a, b) -> np.ndarray:
    """Per-case bit-equality of (pn, elapsed) pairs → bool[n]."""
    pn_eq = (a[0] == b[0]).reshape(len(a[0]), -1).all(axis=1)
    el_eq = (a[1] == b[1]).reshape(len(a[1]), -1).all(axis=1)
    return pn_eq & el_eq


def _states_ge(a, b) -> np.ndarray:
    pn_ge = (a[0] >= b[0]).reshape(len(a[0]), -1).all(axis=1)
    el_ge = (a[1] >= b[1]).reshape(len(a[1]), -1).all(axis=1)
    return pn_ge & el_ge


@dataclasses.dataclass
class JoinDomain:
    """The tiny lattice domain a batched-join model enumerates: B×N state,
    single-delta batches over (row, slot, added, taken, elapsed)."""

    B: int = 2
    N: int = 2
    vals: Tuple[int, ...] = (0, 1, 3)  # idempotence/monotone domain
    pair_vals: Tuple[int, ...] = (0, 3)  # commutativity pair domain

    def deltas(self, vals) -> np.ndarray:
        """→ int64[M, 5] rows of (row, slot, a, t, e)."""
        rows = range(self.B)
        slots = range(self.N)
        return np.array(
            list(itertools.product(rows, slots, vals, vals, vals)), np.int64
        )

    def states(self, vals) -> Tuple[np.ndarray, np.ndarray]:
        """Zero, top, and every single-delta image of zero — the lattice
        points one join step from the seeds. → (pn[M,B,N,2], el[M,B])."""
        top = max(vals)
        pns = [np.zeros((self.B, self.N, 2), np.int64),
               np.full((self.B, self.N, 2), top, np.int64)]
        els = [np.zeros(self.B, np.int64), np.full(self.B, top, np.int64)]
        for r, s, a, t, e in self.deltas(vals):
            pn = np.zeros((self.B, self.N, 2), np.int64)
            pn[r, s, 0], pn[r, s, 1] = a, t
            el = np.zeros(self.B, np.int64)
            el[r] = e
            pns.append(pn)
            els.append(el)
        pn_arr = np.stack(pns)
        el_arr = np.stack(els)
        flat = np.concatenate(
            [pn_arr.reshape(len(pn_arr), -1), el_arr.reshape(len(el_arr), -1)], axis=1
        )
        _, keep = np.unique(flat, axis=0, return_index=True)
        keep.sort()
        return pn_arr[keep], el_arr[keep]


def _model_join_batch(
    root: ProveRoot,
    fn: Callable,
    as_batch: Callable,
    site: Tuple[str, int],
    domain: Optional[JoinDomain] = None,
) -> List[Finding]:
    """Generic model checker for single-delta batched joins (merge_batch,
    merge_batch_folded, merge_rows_dense via adapters): commutativity,
    idempotence under duplication, monotonicity — bit-exact over the
    enumerated domain."""
    import jax

    from patrol_tpu.models.limiter import LimiterState

    dom = domain or JoinDomain()
    findings: List[Finding] = []

    def one(pn, el, d):
        out = fn(LimiterState(pn=pn, elapsed=el), as_batch(d))
        return out.pn, out.elapsed

    app = jax.jit(jax.vmap(one))

    def fmt_delta(d) -> str:
        return f"(row={d[0]}, slot={d[1]}, a={d[2]}, t={d[3]}, e={d[4]})"

    # PTP003 idempotence + PTP004 monotonicity share one grid.
    if "PTP003" in root.obligations or "PTP004" in root.obligations:
        pn0, el0 = dom.states(dom.vals)
        deltas = dom.deltas(dom.vals)
        S_pn, S_el, D = _grid((pn0, el0), (deltas,))
        once = _chunked(app, [S_pn, S_el, D])
        if "PTP003" in root.obligations:
            twice = _chunked(app, [once[0], once[1], D])
            i = _first_bad(_states_eq(twice, once))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP003",
                        *site,
                        f"[{root.name}] join is not idempotent: re-applying "
                        f"delta {fmt_delta(D[i])} moved the state again "
                        "(duplicated packets would diverge replicas)",
                    )
                )
        if "PTP004" in root.obligations:
            i = _first_bad(_states_ge(once, (S_pn, S_el)))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP004",
                        *site,
                        f"[{root.name}] join is not monotone: applying delta "
                        f"{fmt_delta(D[i])} shrank a state plane (a replayed "
                        "stale delta could roll back converged state)",
                    )
                )

    # PTP002 commutativity: two single-delta joins in both orders.
    if "PTP002" in root.obligations:
        pn0, el0 = dom.states(dom.pair_vals)
        deltas = dom.deltas(dom.pair_vals)
        S_pn, S_el, D1, D2 = _grid((pn0, el0), (deltas,), (deltas,))
        ab = _chunked(app, [S_pn, S_el, D1])
        ab = _chunked(app, [ab[0], ab[1], D2])
        ba = _chunked(app, [S_pn, S_el, D2])
        ba = _chunked(app, [ba[0], ba[1], D1])
        i = _first_bad(_states_eq(ab, ba))
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] join does not commute: deltas "
                    f"{fmt_delta(D1[i])} then {fmt_delta(D2[i])} != the "
                    "reverse order (replicas receiving different delivery "
                    "orders would diverge)",
                )
            )
    return findings


def _model_dense_join(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Full-state binary join (merge_dense): commutativity, associativity,
    idempotence, monotonicity over an exhaustive tiny state space."""
    import jax

    from patrol_tpu.models.limiter import LimiterState

    findings: List[Finding] = []
    B, N = 1, 2

    def enum_states(vals) -> Tuple[np.ndarray, np.ndarray]:
        elems = B * N * 2 + B
        combos = np.array(list(itertools.product(vals, repeat=elems)), np.int64)
        pn = combos[:, : B * N * 2].reshape(-1, B, N, 2)
        el = combos[:, B * N * 2 :].reshape(-1, B)
        return pn, el

    def one(pa, ea, pb, eb):
        out = fn(LimiterState(pn=pa, elapsed=ea), LimiterState(pn=pb, elapsed=eb))
        return out.pn, out.elapsed

    app = jax.jit(jax.vmap(one))

    pn0, el0 = enum_states((0, 1, 3))
    A_pn, A_el, B_pn, B_el = _grid((pn0, el0), (pn0, el0))
    ab = _chunked(app, [A_pn, A_el, B_pn, B_el])

    if "PTP002" in root.obligations:
        ba = _chunked(app, [B_pn, B_el, A_pn, A_el])
        i = _first_bad(_states_eq(ab, ba))
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] dense join does not commute: "
                    f"merge(a, b) != merge(b, a) at pn_a={A_pn[i].ravel().tolist()}, "
                    f"pn_b={B_pn[i].ravel().tolist()}",
                )
            )

    if "PTP003" in root.obligations:
        aa = _chunked(app, [pn0, el0, pn0, el0])
        i = _first_bad(_states_eq(aa, (pn0, el0)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] dense join is not idempotent: "
                    f"merge(a, a) != a at pn_a={pn0[i].ravel().tolist()} "
                    f"(anti-entropy replays would inflate state)",
                )
            )

    if "PTP004" in root.obligations:
        ok = _states_ge(ab, (A_pn, A_el)) & _states_ge(ab, (B_pn, B_el))
        i = _first_bad(ok)
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] dense join is not monotone (not an upper "
                    f"bound of its inputs) at pn_a={A_pn[i].ravel().tolist()}, "
                    f"pn_b={B_pn[i].ravel().tolist()}",
                )
            )

    # Associativity rides on PTP002 (order-freedom is the composite claim);
    # a smaller two-value domain keeps the triple enumeration exhaustive.
    if "PTP002" in root.obligations:
        pn2, el2 = enum_states((0, 3))
        A_pn, A_el, B_pn, B_el, C_pn, C_el = _grid(
            (pn2, el2), (pn2, el2), (pn2, el2)
        )
        ab = _chunked(app, [A_pn, A_el, B_pn, B_el])
        ab_c = _chunked(app, [ab[0], ab[1], C_pn, C_el])
        bc = _chunked(app, [B_pn, B_el, C_pn, C_el])
        a_bc = _chunked(app, [A_pn, A_el, bc[0], bc[1]])
        i = _first_bad(_states_eq(ab_c, a_bc))
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] dense join is not associative: "
                    "merge(merge(a,b),c) != merge(a,merge(b,c)) at "
                    f"pn_a={A_pn[i].ravel().tolist()}, "
                    f"pn_b={B_pn[i].ravel().tolist()}, "
                    f"pn_c={C_pn[i].ravel().tolist()}",
                )
            )
    return findings


def _model_tree_converge(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """The hierarchical-converge model (parallel.topology.tree_reduce_states):
    for each replica fan-in R — power-of-two (the distributed butterfly
    schedule) AND ragged (the fallback, where a biased tree could silently
    drop the tail) — the tree reduce of R stacked replica states must

    * equal the FLAT elementwise-max join bit-exactly (PTP002: any
      divergence means a tree-converged replica would disagree with the
      all-gather join the mesh is checked against),
    * be invariant under leaf permutation (PTP002: reduction-tree shape
      and replica order cannot matter),
    * absorb a duplicated leaf (PTP003: a replica counted twice — the
      delta-CRDT re-fold property interior nodes rely on),
    * upper-bound every leaf (PTP004: converge can only move replicas up
      the lattice).
    """
    import jax

    findings: List[Finding] = []
    B, N = 1, 2

    def enum_states(vals) -> Tuple[np.ndarray, np.ndarray]:
        elems = B * N * 2 + B
        combos = np.array(list(itertools.product(vals, repeat=elems)), np.int64)
        pn = combos[:, : B * N * 2].reshape(-1, B, N, 2)
        el = combos[:, B * N * 2 :].reshape(-1, B)
        return pn, el

    pn0, el0 = enum_states((0, 1, 3))
    M = len(pn0)

    def app(spn, sel):
        def one(p, e):
            out = fn(p, e)
            return out.pn, out.elapsed

        return jax.jit(jax.vmap(one))(spn, sel)

    for R in (2, 3, 4, 8):
        # Deterministic sliding-window stacks: every state leads one stack,
        # with its successors (mod M) as the other leaves — M stacks per R,
        # covering every state in every leaf position across the sweep.
        idx = (np.arange(M)[:, None] + np.arange(R)[None, :]) % M
        S_pn = pn0[idx]  # [M, R, B, N, 2]
        S_el = el0[idx]  # [M, R, B]
        want = (S_pn.max(axis=1), S_el.max(axis=1))
        got = _chunked(app, [S_pn, S_el])

        if "PTP002" in root.obligations:
            i = _first_bad(_states_eq(got, want))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP002",
                        *site,
                        f"[{root.name}] tree converge diverges from the flat "
                        f"join at R={R}: reducing "
                        f"pn={S_pn[i].reshape(R, -1).tolist()} through the "
                        "tree != the elementwise max (replicas on different "
                        "reduction paths would disagree)",
                    )
                )
            perm = np.roll(np.arange(R), 1)
            got_p = _chunked(app, [S_pn[:, perm], S_el[:, perm]])
            i = _first_bad(_states_eq(got_p, got))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP002",
                        *site,
                        f"[{root.name}] tree converge is leaf-order "
                        f"dependent at R={R}: permuting the replica stack "
                        "changed the join (reduction-tree shape must not "
                        "matter)",
                    )
                )

        if "PTP003" in root.obligations:
            dup_pn = np.concatenate([S_pn, S_pn[:, :1]], axis=1)
            dup_el = np.concatenate([S_el, S_el[:, :1]], axis=1)
            got_d = _chunked(app, [dup_pn, dup_el])
            i = _first_bad(_states_eq(got_d, want))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP003",
                        *site,
                        f"[{root.name}] tree converge is not idempotent "
                        f"under a duplicated leaf at R={R}+1 (a replica "
                        "heard twice through two tree paths would inflate "
                        "the join)",
                    )
                )

        if "PTP004" in root.obligations:
            ok_pn = (got[0][:, None] >= S_pn).all(axis=(1, 2, 3, 4))
            ok_el = (got[1][:, None] >= S_el).all(axis=(1, 2))
            i = _first_bad(ok_pn & ok_el)
            if i is not None:
                findings.append(
                    Finding(
                        "PTP004",
                        *site,
                        f"[{root.name}] tree converge is not an upper bound "
                        f"of its replica inputs at R={R} (converge rolled a "
                        "replica's state back down the lattice)",
                    )
                )
    return findings


def _model_take_monotone(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """PTP004 for the take kernel: a take may only GROW the PN lanes and
    elapsed (monotone G-counters), and only its own node lane — enumerated
    over a small grid of states × requests."""
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import NANO, LimiterState
    from patrol_tpu.ops.take import TakeRequest

    findings: List[Finding] = []
    node_slot = 0
    dom = JoinDomain(B=2, N=2, vals=(0, NANO, 3 * NANO))
    pn0, el0 = dom.states(dom.vals)

    reqs = np.array(
        [
            (row, now, freq, per, count, nreq, cap, created)
            for row in (0, 1)
            for now in (0, NANO, 3 * NANO)
            for freq in (0, 2)
            for per in (0, NANO)
            for count in (0, NANO)
            for nreq in (0, 2)
            for cap in (0, 2 * NANO)
            for created in (0, NANO)
        ],
        np.int64,
    )

    def one(pn, el, r):
        req = TakeRequest(
            rows=r[0].astype(jnp.int32)[None],
            now_ns=r[1][None],
            freq=r[2][None],
            per_ns=r[3][None],
            count_nt=r[4][None],
            nreq=r[5][None],
            cap_base_nt=r[6][None],
            created_ns=r[7][None],
        )
        out, _res = fn(LimiterState(pn=pn, elapsed=el), req, node_slot)
        return out.pn, out.elapsed

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, R = _grid((pn0, el0), (reqs,))
    out = _chunked(app, [S_pn, S_el, R])

    i = _first_bad(_states_ge(out, (S_pn, S_el)))
    if i is not None:
        findings.append(
            Finding(
                "PTP004",
                *site,
                f"[{root.name}] take shrank a state plane at request "
                f"{R[i].tolist()}: lanes must stay monotone G-counters or "
                "max-joins resurrect forfeited tokens",
            )
        )

    other = np.ones(pn0.shape[1:3], bool)
    other[:, node_slot] = False
    locality = (out[0][:, other] == S_pn[:, other]).reshape(len(S_pn), -1).all(axis=1)
    i = _first_bad(locality)
    if i is not None:
        findings.append(
            Finding(
                "PTP004",
                *site,
                f"[{root.name}] take wrote a PN lane other than its own "
                f"(node_slot={node_slot}) at request {R[i].tolist()}: remote "
                "lanes may change only by max-merge",
            )
        )
    return findings


def _model_take_n_laws(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """The coalesced take-n serving kernel, checked bit-exactly over a
    small states × requests grid:

    * PTP002 — hot-key coalescing is exact: ONE packed row carrying
      ``nreq = n`` commits the same state and admits the same count as
      n sequential ``nreq = 1`` applications of the same request at the
      same timestamp (the reference's serialized takes, where only the
      first sees a refill). The replay leg runs the CERTIFIED per-ticket
      kernel — not ``fn`` — so a seeded defect in the checked kernel
      cannot vouch for itself by breaking both legs identically. This is
      the law that lets the feeder fold a Zipf crowd into one dispatch
      without changing a single outcome.
    * PTP003 — deny fixpoint: a row admitting zero commits NOTHING, so
      replaying a denied crowd any number of times never moves state
      (a deny storm must not drift the bucket).
    * PTP004 — monotone lanes + own-lane locality, as take_monotone.
    """
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import NANO, LimiterState
    from patrol_tpu.ops.take import take_n_batch as _reference_take_n

    findings: List[Finding] = []
    node_slot = 0
    max_n = 3  # the grid's largest crowd; unrolled in the replay below
    dom = JoinDomain(B=2, N=2, vals=(0, NANO, 3 * NANO))
    pn0, el0 = dom.states(dom.vals)

    reqs = np.array(
        [
            (row, now, freq, per, count, nreq, cap, created)
            for row in (0, 1)
            for now in (0, NANO, 3 * NANO)
            for freq in (0, 2)
            for per in (0, NANO)
            for count in (0, NANO)
            for nreq in (0, 1, max_n)
            for cap in (0, 2 * NANO)
            for created in (0, NANO)
        ],
        np.int64,
    )

    def one(pn, el, r):
        packed = r[:, None]  # the kernel's [TAKE_PACK_ROWS, K=1] layout
        b_state, b_out = fn(LimiterState(pn=pn, elapsed=el), packed, node_slot)

        # Sequential replay on the certified per-ticket kernel: max_n
        # unit takes at the same timestamp, step j live iff j < nreq
        # (an nreq=0 row is the kernel's own padding no-op, so the
        # unroll is exact for every grid n).
        seq = LimiterState(pn=pn, elapsed=el)
        seq_adm = jnp.zeros((1,), jnp.int64)
        for j in range(max_n):
            unit = packed.at[5, 0].set(
                jnp.where(j < r[5], jnp.int64(1), jnp.int64(0))
            )
            seq, s_out = _reference_take_n(seq, unit, node_slot)
            seq_adm = seq_adm + s_out[1]
        return b_state.pn, b_state.elapsed, b_out[1], seq.pn, seq.elapsed, seq_adm

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, R = _grid((pn0, el0), (reqs,))
    b_pn, b_el, b_adm, s_pn, s_el, s_adm = _chunked(app, [S_pn, S_el, R])

    if "PTP002" in root.obligations:
        eq = _states_eq((b_pn, b_el), (s_pn, s_el)) & (
            b_adm[:, 0] == s_adm[:, 0]
        )
        i = _first_bad(eq)
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] coalesced take-n diverges from the "
                    f"sequential replay at request {R[i].tolist()}: one "
                    "row with nreq=n must commit exactly what n unit "
                    "takes at the same timestamp commit (admitted "
                    f"{int(b_adm[i, 0])} vs {int(s_adm[i, 0])})",
                )
            )

    if "PTP003" in root.obligations:
        denied = b_adm[:, 0] == 0
        moved = ~_states_eq((b_pn, b_el), (S_pn, S_el))
        i = _first_bad(~(denied & moved))
        if i is not None:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] a fully denied row mutated state at "
                    f"request {R[i].tolist()}: denies must be a fixpoint "
                    "or a replayed deny storm drifts the bucket",
                )
            )

    if "PTP004" in root.obligations:
        i = _first_bad(_states_ge((b_pn, b_el), (S_pn, S_el)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] take-n shrank a state plane at "
                    f"request {R[i].tolist()}: lanes must stay monotone "
                    "G-counters or max-joins resurrect forfeited tokens",
                )
            )
        other = np.ones(pn0.shape[1:3], bool)
        other[:, node_slot] = False
        locality = (
            (b_pn[:, other] == S_pn[:, other]).reshape(len(S_pn), -1).all(axis=1)
        )
        i = _first_bad(locality)
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] take-n wrote a PN lane other than its "
                    f"own (node_slot={node_slot}) at request "
                    f"{R[i].tolist()}: remote lanes change only by merge",
                )
            )
    return findings


def _model_take_split_fifo(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """The host-side grant split behind take-n coalescing: ``fn`` fans
    one coalesced row's ``(have, admitted, count, nreq)`` out to
    per-ticket ``(remaining, ok)`` responses, exhaustively checked
    against an explicit sequential ledger replay:

    * PTP002 — FIFO first-k-of-m: ticket i (0-based arrival order)
      succeeds iff ``i < admitted``; each admitted ticket sees the
      balance after its OWN commit, each denied ticket the balance
      after ALL admitted commits. A LIFO or round-robin split — late
      arrivals jumping the crowd — is a counterexample here.
    * PTP003 — deny storm: an ``admitted == 0`` row hands every ticket
      the SAME untouched balance with ``ok = False`` (clamped at zero:
      PN merges can drive it negative) — the reported balance must not
      walk down a ledger nobody spent.
    """
    from patrol_tpu.models.limiter import NANO

    findings: List[Finding] = []
    want_002 = "PTP002" in root.obligations
    want_003 = "PTP003" in root.obligations
    haves = (-NANO, 0, NANO // 2, NANO, 2 * NANO, 3 * NANO, 5 * NANO + 7)
    for have in haves:
        for count in (NANO, 2 * NANO):
            for nreq in range(5):
                for admitted in range(nreq + 1):
                    got = [
                        (int(r), bool(ok))
                        for r, ok in fn(have, admitted, count, nreq)
                    ]
                    bal = have
                    want = []
                    for i in range(admitted):
                        bal -= count
                        want.append((max(bal, 0) // NANO, True))
                    post = max(have - admitted * count, 0) // NANO
                    want.extend((post, False) for _ in range(admitted, nreq))
                    if want_002 and got != want:
                        findings.append(
                            Finding(
                                "PTP002",
                                *site,
                                f"[{root.name}] grant split diverges from "
                                "the FIFO first-k-of-m ledger at "
                                f"(have={have}, admitted={admitted}, "
                                f"count={count}, nreq={nreq}): got {got}, "
                                f"sequential replay says {want}",
                            )
                        )
                        want_002 = False  # first counterexample suffices
                    if want_003 and admitted == 0 and nreq > 0:
                        fixed = (max(have, 0) // NANO, False)
                        if any(entry != fixed for entry in got):
                            findings.append(
                                Finding(
                                    "PTP003",
                                    *site,
                                    f"[{root.name}] deny storm drifted the "
                                    f"reported balance at (have={have}, "
                                    f"count={count}, nreq={nreq}): every "
                                    f"denied ticket must see {fixed}, got "
                                    f"{got}",
                                )
                            )
                            want_003 = False
    return findings


def _model_scalar_monotone(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """PTP004 for the deficit-attribution scalar merge: monotone, and
    writes only the sender's lane. (It is deliberately NOT a full CvRDT
    join — the reference's scalar semantics are lossy by design — so no
    PTP002/PTP003 obligations are declared for it.)"""
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import LimiterState
    from patrol_tpu.ops.merge import MergeBatch

    findings: List[Finding] = []
    dom = JoinDomain(B=2, N=2, vals=(0, 1, 3))
    pn0, el0 = dom.states(dom.vals)
    deltas = dom.deltas(dom.vals)

    def one(pn, el, d):
        batch = MergeBatch(
            rows=d[0].astype(jnp.int32)[None],
            slots=d[1].astype(jnp.int32)[None],
            added_nt=d[2][None],
            taken_nt=d[3][None],
            elapsed_ns=d[4][None],
        )
        out = fn(LimiterState(pn=pn, elapsed=el), batch)
        return out.pn, out.elapsed

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, D = _grid((pn0, el0), (deltas,))
    out = _chunked(app, [S_pn, S_el, D])
    i = _first_bad(_states_ge(out, (S_pn, S_el)))
    if i is not None:
        findings.append(
            Finding(
                "PTP004",
                *site,
                f"[{root.name}] scalar merge shrank a state plane at delta "
                f"(row={D[i][0]}, slot={D[i][1]}, a={D[i][2]}, t={D[i][3]}, "
                f"e={D[i][4]})",
            )
        )

    # Locality: only the sender's (row, slot) PN cell may move.
    moved = out[0] != S_pn  # [M, B, N, 2]
    idx = np.arange(len(D))
    own = np.zeros_like(moved)
    own[idx, D[:, 0], D[:, 1], :] = True
    cell = (moved & ~own).reshape(len(D), -1).any(axis=1)
    i = _first_bad(~cell)
    if i is not None:
        findings.append(
            Finding(
                "PTP004",
                *site,
                f"[{root.name}] scalar merge wrote a PN cell other than the "
                f"sender's (row={D[i][0]}, slot={D[i][1]})",
            )
        )
    return findings


def _model_lifecycle_iszero(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """The bucket-lifecycle conservation suite (idle-bucket GC). The
    predicate under test says "this bucket is reconstructible from its
    rate — drop it". Each declared algebraic code maps onto the law that
    makes that drop safe, checked bit-exactly over an enumerated domain:

    * **PTP002 — soundness (admitted-token conservation).** Wherever the
      predicate says *full*, a take against the ORIGINAL row and a take
      against a FRESH re-created row (zero lanes, ``elapsed=0``,
      ``created=now_gc``) must produce identical ``(have, admitted)``
      through the real take kernel, at the sweep instant and later. A
      verdict that fires on a non-full bucket forgets un-refilled spend
      — the re-created bucket would admit more than the original.
    * **PTP004 — time-monotonicity.** ``full(s, now)`` implies
      ``full(s, now')`` for every ``now' >= now`` (no new spend): a
      sweep window missed can only delay a reclaim, never invalidate
      one, so GC pressure ramps are safe.
    * **PTP003 — re-entry exactness.** Zero lanes are the join's bottom
      (``merge_dense(0, s) == s``) — dropped state re-entering via the
      max-lattice join reconstructs the peer's view exactly — and the
      verdict is stable under self-join (``full(s ⊔ s) == full(s)``),
      so duplicated re-entry cannot flip a reclaim decision.
    """
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import NANO, LimiterState
    from patrol_tpu.ops.lifecycle import LifecycleProbe
    from patrol_tpu.ops.merge import merge_dense
    from patrol_tpu.ops.take import TakeRequest, take_batch

    findings: List[Finding] = []
    node_slot = 0
    dom = JoinDomain(B=2, N=2, vals=(0, NANO, 3 * NANO))
    pn0, el0 = dom.states(dom.vals)

    probes = np.array(
        [
            (row, now, per, cap, created)
            for row in (0, 1)
            for now in (0, NANO, 4 * NANO)
            for per in (0, NANO)
            for cap in (0, NANO, 2 * NANO)
            for created in (0, NANO)
        ],
        np.int64,
    )

    def verdict(pn, el, p):
        out = fn(
            LimiterState(pn=pn, elapsed=el),
            LifecycleProbe(
                rows=p[0].astype(jnp.int32)[None],
                now_ns=p[1][None],
                per_ns=p[2][None],
                cap_base_nt=p[3][None],
                created_ns=p[4][None],
            ),
            node_slot,
        )
        return out.full[0]

    v_app = jax.jit(jax.vmap(verdict))
    S_pn, S_el, P = _grid((pn0, el0), (probes,))
    (full,) = _chunked(v_app, [S_pn, S_el, P])
    full = full.astype(bool)

    def fmt(i) -> str:
        p = P[i]
        return (
            f"(row={p[0]}, now={p[1]}, per={p[2]}, cap={p[3]}, "
            f"created={p[4]}, pn={S_pn[i].ravel().tolist()}, "
            f"el={S_el[i].ravel().tolist()})"
        )

    # PTP002 — soundness: first-take observation equivalence vs a fresh
    # re-created row, at the sweep instant and one period later.
    if "PTP002" in root.obligations and full.any():
        sel = np.flatnonzero(full)
        fresh_pn = np.zeros_like(S_pn[sel])
        fresh_el = np.zeros_like(S_el[sel])

        def take_have(pn, el, p, off, created):
            req = TakeRequest(
                rows=p[0].astype(jnp.int32)[None],
                now_ns=(p[1] + off)[None],
                freq=(p[3] // NANO)[None],
                per_ns=p[2][None],
                count_nt=jnp.int64(NANO)[None],
                nreq=jnp.int64(2)[None],
                cap_base_nt=p[3][None],
                created_ns=created[None],
            )
            _, res = take_batch(LimiterState(pn=pn, elapsed=el), req, node_slot)
            return res.have_nt[0], res.admitted[0]

        t_app = jax.jit(jax.vmap(take_have))
        for off in (0, NANO):
            offs = np.full(len(sel), off, np.int64)
            h_old = _chunked(
                t_app, [S_pn[sel], S_el[sel], P[sel], offs, P[sel][:, 4]]
            )
            # Fresh row: created at the sweep instant (probe.now).
            h_new = _chunked(
                t_app, [fresh_pn, fresh_el, P[sel], offs, P[sel][:, 1]]
            )
            bad = ~((h_old[0] == h_new[0]) & (h_old[1] == h_new[1]))
            i = _first_bad(~bad)
            if i is not None:
                j = sel[i]
                findings.append(
                    Finding(
                        "PTP002",
                        *site,
                        f"[{root.name}] IsZero verdict is unsound at "
                        f"{fmt(j)}+{off}ns: a take against the reclaimed-"
                        f"and-recreated row gives (have={h_new[0][i]}, "
                        f"admitted={h_new[1][i]}) but the original row "
                        f"gives (have={h_old[0][i]}, admitted="
                        f"{h_old[1][i]}) — reclaiming here loses admitted "
                        "tokens (or invents new ones)",
                    )
                )
                break

    # PTP004 — the verdict is monotone in time.
    if "PTP004" in root.obligations:
        for off in (1, NANO, 16 * NANO):
            P2 = P.copy()
            P2[:, 1] += off
            (full2,) = _chunked(v_app, [S_pn, S_el, P2])
            i = _first_bad(~(full & ~full2.astype(bool)))
            if i is not None:
                findings.append(
                    Finding(
                        "PTP004",
                        *site,
                        f"[{root.name}] IsZero verdict is not monotone in "
                        f"time at {fmt(i)}: full now but not full {off}ns "
                        "later with no new spend — a delayed sweep would "
                        "wrongly keep (or wrongly drop) the bucket",
                    )
                )
                break

    # PTP003 — re-entry: zero is the join's bottom, and the verdict is
    # stable under self-join (duplicated re-entry).
    if "PTP003" in root.obligations:
        def join0(pn, el):
            z = LimiterState(
                pn=jnp.zeros_like(pn), elapsed=jnp.zeros_like(el)
            )
            out = merge_dense(z, LimiterState(pn=pn, elapsed=el))
            return out.pn, out.elapsed

        j_app = jax.jit(jax.vmap(join0))
        back = _chunked(j_app, [pn0, el0])
        i = _first_bad(_states_eq(back, (pn0, el0)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] zero lanes are not the join's bottom "
                    f"at pn={pn0[i].ravel().tolist()}: a reclaimed bucket "
                    "re-entering via the max-lattice join would not "
                    "reconstruct the peer's view exactly",
                )
            )

        def self_join(pn, el):
            s = LimiterState(pn=pn, elapsed=el)
            return merge_dense(s, s).pn, merge_dense(s, s).elapsed

        sj_app = jax.jit(jax.vmap(self_join))
        joined = _chunked(sj_app, [S_pn, S_el])
        (full_j,) = _chunked(v_app, [joined[0], joined[1], P])
        i = _first_bad(full == full_j.astype(bool))
        if i is not None:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] IsZero verdict flips under self-join "
                    f"at {fmt(i)}: duplicated re-entry of the same state "
                    "changed a reclaim decision",
                )
            )
    return findings


def _model_rate_algebra(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Rate algebra: PTP004 tokens monotone in elapsed time; PTP003
    parse/format round-trip stability (pure Python, exhaustive grids)."""
    from patrol_tpu.ops import rate as rate_mod

    findings: List[Finding] = []

    if "PTP004" in root.obligations:
        d_grid = [0, 1, 2, 5, 10**6, 10**9, 2 * 10**9, 10**12]
        for freq in (0, 1, 2, 3, 7, 50):
            for per in (0, 1, 3, 10**6, 10**9, 60 * 10**9):
                r = rate_mod.Rate(freq=freq, per_ns=per)
                toks = [r.tokens(d) for d in d_grid]
                if any(b < a for a, b in zip(toks, toks[1:])):
                    findings.append(
                        Finding(
                            "PTP004",
                            *site,
                            f"[{root.name}] Rate({freq}:{per}ns).tokens is "
                            "not monotone in elapsed time",
                        )
                    )
                    break
            else:
                continue
            break

    if "PTP003" in root.obligations:
        for freq in (0, 1, 2, 50, 10**6):
            for per in ("1s", "500ms", "1m30s", "1h", "1ns", "2h45m"):
                r = rate_mod.Rate(freq=freq, per_ns=rate_mod.parse_duration(per))
                back = rate_mod.parse_rate(str(r))
                if back != r:
                    findings.append(
                        Finding(
                            "PTP003",
                            *site,
                            f"[{root.name}] parse(format({r})) = {back}: "
                            "rate round-trip is not stable",
                        )
                    )
    return findings


def _model_wire_roundtrip(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Wire codec: PTP003 decode∘encode identity and re-encode stability
    over every trailer form, plus scalar/vector sanitize agreement —
    replicas decoding the same packet MUST land on the same state."""
    import math

    from patrol_tpu.ops import wire

    findings: List[Finding] = []
    NANO = wire.NANO

    def ws(**kw) -> wire.WireState:
        base = dict(name="b", added=1.5, taken=0.5, elapsed_ns=7)
        base.update(kw)
        return wire.WireState(**base)

    states = []
    for name in ("", "a", "bucket-µ≠ascii"):
        for added, taken, elapsed in ((0.0, 0.0, 0), (1.5, 0.5, 7), (9.0, 2.0, -5)):
            states.append(ws(name=name, added=added, taken=taken, elapsed_ns=elapsed))
            states.append(
                ws(name=name, added=added, taken=taken, elapsed_ns=elapsed,
                   origin_slot=3)
            )
            states.append(
                ws(name=name, added=added, taken=taken, elapsed_ns=elapsed,
                   origin_slot=3, multi_ok=True)
            )
            states.append(
                ws(name=name, added=added, taken=taken, elapsed_ns=elapsed,
                   origin_slot=3, cap_nt=10 * NANO)
            )
            states.append(
                ws(name=name, added=added, taken=taken, elapsed_ns=elapsed,
                   origin_slot=3, cap_nt=10 * NANO, lane_added_nt=2 * NANO,
                   lane_taken_nt=NANO)
            )
            states.append(
                ws(name=name, added=added, taken=taken, elapsed_ns=elapsed,
                   origin_slot=1, cap_nt=10 * NANO,
                   lanes=((0, NANO, 0), (1, 2 * NANO, NANO)), multi_ok=True)
            )

    for s in states:
        pkt = wire.encode(s)
        back = wire.decode(pkt)
        if back != s:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] decode(encode(x)) != x for {s!r}: wire "
                    "round-trip must be exact or replicas fork on relay",
                )
            )
            break
        if wire.encode(back) != pkt:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] re-encode of a decoded packet is not "
                    f"byte-stable for {s!r}",
                )
            )
            break

    hostile = [
        0.0, -1.0, -0.0, 0.5, 1.5, 1e30, float("inf"), float("-inf"),
        float("nan"), float(2**53), 9.3e9, 1e-12, (1 << 62) / NANO,
    ]
    vec = wire.sanitize_nt_array(hostile)
    for i, v in enumerate(hostile):
        scalar = wire._sanitize_nt(v)
        if int(vec[i]) != scalar:
            shown = "nan" if math.isnan(v) else repr(v)
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] sanitize divergence at {shown}: scalar="
                    f"{scalar} vector={int(vec[i])} — native-rx and "
                    "python-rx peers would merge the same packet differently",
                )
            )
            break
    return findings


def _model_delta_roundtrip(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Wire-v2 delta codec: PTP003 decode∘encode identity over a grid of
    names/values/ack vectors, re-encode byte-stability, strict rejection
    of every truncation, and single-byte-corruption detection — replicas
    must either merge an interval exactly or not at all."""
    from patrol_tpu.ops import wire

    findings: List[Finding] = []

    def bad(msg: str) -> None:
        findings.append(Finding("PTP003", *site, f"[{root.name}] {msg}"))

    big = (1 << 62) + 7
    names = ["", "a", "bucket-µ≠ascii", "x" * 200]
    vals = [0, 1, big]
    entries = [
        wire.DeltaEntry(n, s, c, a, t, e)
        for n in names
        for s in (0, 3)
        for c, a, t, e in ((0, 0, 0, 0), (vals[2], 1, 2, 3), (5, big, big, big))
    ]
    cases = [
        (0, (), ()),  # bare ack, empty vector
        (1, (1, 2, 3), tuple(entries[:4])),
        (0xFFFFFFFF, tuple(range(100, 132)), tuple(entries)),
        (7, (), tuple(entries[:1])),
    ]
    for seq, acks, ents in cases:
        pkt, n = fn(3, seq, acks, ents)
        back = wire.decode_delta_packet(pkt)
        if back is None:
            bad(f"decode(encode(...)) rejected a legal interval (seq={seq})")
            break
        expect = wire.DeltaPacket(3, seq, tuple(acks)[:wire.DELTA_MAX_ACKS], tuple(ents[:n]))
        if back != expect:
            bad(
                f"decode(encode(x)) != x at seq={seq}: interval round-trip "
                "must be exact or replicas fork on relay"
            )
            break
        repkt, _ = fn(back.sender_slot, back.seq, back.acks, back.entries)
        if repkt != pkt:
            bad(f"re-encode of a decoded interval is not byte-stable (seq={seq})")
            break
    if not findings:
        pkt, n = fn(1, 9, (4, 5), tuple(entries[:6]))
        for i in range(len(pkt)):
            if wire.decode_delta_packet(pkt[:i]) is not None:
                bad(f"truncation to {i} bytes decoded as a valid interval")
                break
        for i in range(len(pkt)):
            flipped = bytearray(pkt)
            flipped[i] ^= 0x41
            # Envelope flips break the reserved-name check; body flips
            # break the checksum: every single-byte corruption must be
            # rejected whole (faultnet's corrupt schedules rely on it).
            if wire.decode_delta_packet(bytes(flipped)) is not None:
                bad(f"byte flip at offset {i} went undetected")
                break
    return findings


def _model_raw_ingest(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Device-resident ingest kernel (ops/ingest.py decode_fold_raw):
    the raw-plane decode+fold dispatch checked against the python wire
    decoder + reference join over real dv2 datagram bytes — PTP002
    packet-order commutativity AND bit-agreement with the decoder,
    PTP003 duplicated-plane idempotence plus strict all-or-nothing
    corruption rejection (verdicts must match wire.decode_delta_packet
    on every truncation and byte flip, and a rejected packet must merge
    NOTHING), PTP004 join monotonicity over a pre-seeded state."""
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import LimiterConfig, init_state
    from patrol_tpu.ops import ingest as ingest_ops
    from patrol_tpu.ops import wire

    findings: List[Finding] = []

    def bad(code: str, msg: str) -> None:
        findings.append(Finding(code, *site, f"[{root.name}] {msg}"))

    B, N = 8, 2
    cfg = LimiterConfig(buckets=B, nodes=N)
    ROW = 512
    E = ingest_ops.max_entries(ROW)
    names = ["a", "b", "", "bucket-µ"]
    name_rows = {nm: i for i, nm in enumerate(names)}
    big = (1 << 62) + 5
    ents = [
        wire.DeltaEntry(nm, s, c, a, t, e)
        for nm in names
        for s in (0, 1)
        for c, a, t, e in ((0, 0, 0, 0), (3, 1, 2, big), (5, big, 4, 1))
    ]
    pkts: List[bytes] = []
    i = 0
    while i < len(ents):
        data, k = wire.encode_delta_packet(
            1, len(pkts) + 1, (7,), ents[i:], max_size=ROW
        )
        pkts.append(data)
        i += k

    def planes_of(packets):
        pl = np.full((len(packets), ROW), 0xA5, np.uint8)  # stale tails
        ln = np.zeros(len(packets), np.int32)
        for j, b in enumerate(packets):
            pl[j, : len(b)] = np.frombuffer(b, np.uint8)
            ln[j] = len(b)
        return pl, ln

    def rows_of(packets):
        rws = np.full((len(packets), E), 10**9, np.int32)
        for j, b in enumerate(packets):
            pk = wire.decode_delta_packet(b)
            if pk is None:
                continue
            for k, e in enumerate(pk.entries):
                rws[j, k] = name_rows.get(e.name, 10**9)
        return rws

    def run(packets, state=None):
        pl, ln = planes_of(packets)
        rws = rows_of(packets)
        walk = ingest_ops.host_walk(pl, ln)
        eoff = np.maximum(walk.name_off - 1, 0)
        st = init_state(cfg) if state is None else state
        out = fn(
            st, jnp.asarray(pl), jnp.asarray(ln), jnp.asarray(eoff),
            jnp.asarray(rws),
            jnp.asarray(np.zeros((len(packets), E), bool)),
        )
        return (
            (np.asarray(out[0].pn), np.asarray(out[0].elapsed)),
            np.asarray(out[1]),
        )

    def eq(a, b) -> bool:
        return np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])

    base, ok = run(pkts)
    ref_pn = np.zeros((B, N, 2), np.int64)
    ref_el = np.zeros(B, np.int64)
    for b in pkts:
        pk = wire.decode_delta_packet(b)
        for e in pk.entries:
            r = name_rows[e.name]
            if e.slot >= N:
                continue
            ref_pn[r, e.slot, 0] = max(ref_pn[r, e.slot, 0], e.added_nt)
            ref_pn[r, e.slot, 1] = max(ref_pn[r, e.slot, 1], e.taken_nt)
            ref_el[r] = max(ref_el[r], max(e.elapsed_ns, 0))
    if "PTP002" in root.obligations:
        if not ok.all():
            bad("PTP002", "legal delta-interval planes rejected by the verdict")
        if not eq(base, (ref_pn, ref_el)):
            bad(
                "PTP002",
                "raw-plane decode+fold disagrees with the python decoder + "
                "reference join on the same datagram bytes",
            )
        rev, _ = run(pkts[::-1])
        if not eq(rev, base):
            bad(
                "PTP002",
                "raw ingest is packet-order dependent: reversed plane order "
                "produced a different state",
            )
    if "PTP003" in root.obligations:
        dup, _ = run(pkts + pkts)
        if not eq(dup, base):
            bad("PTP003", "raw ingest is not idempotent under duplicated planes")
        # Corruption sweep: the kernel's verdicts must match the python
        # decoder's on every truncation and byte flip of a real packet,
        # and rejected planes must merge NOTHING (one batch per sweep).
        probe = pkts[0]
        variants = [probe[:j] for j in range(len(probe))]
        variants += [
            bytes(probe[:j]) + bytes([probe[j] ^ 0x41]) + bytes(probe[j + 1:])
            for j in range(len(probe))
        ]
        want = np.array(
            [wire.decode_delta_packet(v) is not None for v in variants]
        )
        got_state, got_ok = run(variants)
        if not np.array_equal(got_ok, want):
            j = _first_bad(got_ok == want)
            bad(
                "PTP003",
                f"verdict diverges from wire.decode_delta_packet on hostile "
                f"variant {j} (truncation/flip sweep): all-or-nothing "
                "validation is the replica-fork guard",
            )
        # Every surviving variant carries probe's own entries (absolute
        # values ⇒ idempotent); rejected ones contribute nothing — so the
        # fold must equal the accepted-subset reference.
        sub_pn = np.zeros((B, N, 2), np.int64)
        sub_el = np.zeros(B, np.int64)
        for v in variants:
            pk = wire.decode_delta_packet(v)
            if pk is None:
                continue
            for e in pk.entries:
                r = name_rows[e.name]
                if e.slot >= N:
                    continue
                sub_pn[r, e.slot, 0] = max(sub_pn[r, e.slot, 0], e.added_nt)
                sub_pn[r, e.slot, 1] = max(sub_pn[r, e.slot, 1], e.taken_nt)
                sub_el[r] = max(sub_el[r], max(e.elapsed_ns, 0))
        if not eq(got_state, (sub_pn, sub_el)):
            bad(
                "PTP003",
                "a rejected (or corrupted) plane leaked values into state: "
                "invalid packets must merge nothing",
            )
    if "PTP004" in root.obligations:
        from patrol_tpu.models.limiter import LimiterState

        seed_pn = np.zeros((B, N, 2), np.int64)
        seed_pn[:4, :, :] = 2
        seed_el = np.full(B, 3, np.int64)
        seeded = LimiterState(
            pn=jnp.asarray(seed_pn), elapsed=jnp.asarray(seed_el)
        )
        grown, _ = run(pkts, state=seeded)
        if not (
            (grown[0] >= seed_pn).all() and (grown[1] >= seed_el).all()
        ):
            bad("PTP004", "raw ingest shrank a state plane: join must be monotone")
    return findings


# ---------------------------------------------------------------------------
# Cert-kit kernel families (ops/gcra.py, ops/concurrency.py,
# ops/hierquota.py). Each model replays the kernel's *sequential
# contract* literally in python — request-by-request, no closed forms —
# and bit-compares the whole (state, admitted) outcome. The replay
# subsumes own-lane locality and elapsed-freeze (the expected state is
# built from the reference and compared whole), so PTP002 here is the
# strong obligation the cert stage's seeded mutations must trip.


def _model_gcra_laws(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """GCRA laws: PTP002 bit-agreement with a literal request-by-request
    replay of the algorithm (conformance against the advancing virtual
    TAT), PTP004 monotonicity — the TAT lane is a max register and may
    never move down the lattice."""
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import TAKEN, LimiterState
    from patrol_tpu.ops.gcra import GcraRequest

    findings: List[Finding] = []
    node_slot = 0
    dom = JoinDomain(B=2, N=2, vals=(0, 2, 5))
    pn0, el0 = dom.states(dom.vals)

    reqs = np.array(
        [
            (row, now, t, tol, nreq)
            for row in (0, 1)
            for now in (0, 2, 5)
            for t in (0, 1, 2)
            for tol in (0, 1, 3)
            for nreq in (0, 1, 3)
        ],
        np.int64,
    )

    def one(pn, el, r):
        req = GcraRequest(
            rows=r[0].astype(jnp.int32)[None],
            now_ns=r[1][None],
            emission_ns=r[2][None],
            tol_ns=r[3][None],
            nreq=r[4][None],
        )
        out, res = fn(LimiterState(pn=pn, elapsed=el), req, node_slot)
        return out.pn, out.elapsed, res.admitted[0]

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, R = _grid((pn0, el0), (reqs,))
    out_pn, out_el, admitted = _chunked(app, [S_pn, S_el, R])

    if "PTP002" in root.obligations:
        n = len(S_pn)
        exp_pn = S_pn.copy()
        exp_adm = np.zeros(n, np.int64)
        for i in range(n):
            row, now, t, tol, nreq = (int(v) for v in R[i])
            tat = int(S_pn[i, row, :, TAKEN].max())
            k = 0
            while k < nreq and t > 0 and tat <= now + tol:
                tat = max(tat, now) + t
                k += 1
            exp_adm[i] = k
            if k:
                lane = exp_pn[i, row, node_slot, TAKEN]
                exp_pn[i, row, node_slot, TAKEN] = max(int(lane), tat)
        i = _first_bad(
            (admitted == exp_adm)
            & _states_eq((out_pn, out_el), (exp_pn, S_el))
        )
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] GCRA diverged from the sequential "
                    f"replay at request {R[i].tolist()}: admitted="
                    f"{int(admitted[i])} expected {int(exp_adm[i])} (or a "
                    "lane other than the own TAT register moved)",
                )
            )

    if "PTP004" in root.obligations:
        i = _first_bad(_states_ge((out_pn, out_el), (S_pn, S_el)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] GCRA shrank a state plane at request "
                    f"{R[i].tolist()}: the TAT lane is a max register and "
                    "must stay monotone or joins resurrect spent windows",
                )
            )
    return findings


def _model_conc_laws(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Concurrency-limit laws: PTP002 bit-agreement with a literal
    release-then-acquire replay (release clamped to the OWN lane pair —
    the phantom-release guard), PTP004 monotonicity of the paired
    G-counter lanes."""
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import ADDED, TAKEN, LimiterState
    from patrol_tpu.ops.concurrency import ConcRequest

    findings: List[Finding] = []
    node_slot = 0
    dom = JoinDomain(B=2, N=2, vals=(0, 1, 3))
    pn0, el0 = dom.states(dom.vals)

    reqs = np.array(
        [
            (row, limit, count, nreq, rel)
            for row in (0, 1)
            for limit in (0, 2, 5)
            for count in (0, 1, 2)
            for nreq in (0, 1, 3)
            for rel in (0, 1, 4)
        ],
        np.int64,
    )

    def one(pn, el, r):
        req = ConcRequest(
            rows=r[0].astype(jnp.int32)[None],
            limit_nt=r[1][None],
            count_nt=r[2][None],
            nreq=r[3][None],
            releases=r[4][None],
        )
        out, res = fn(LimiterState(pn=pn, elapsed=el), req, node_slot)
        return out.pn, out.elapsed, res.admitted[0], res.released_nt[0]

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, R = _grid((pn0, el0), (reqs,))
    out_pn, out_el, admitted, released = _chunked(app, [S_pn, S_el, R])

    if "PTP002" in root.obligations:
        n = len(S_pn)
        exp_pn = S_pn.copy()
        exp_adm = np.zeros(n, np.int64)
        exp_rel = np.zeros(n, np.int64)
        for i in range(n):
            row, limit, count, nreq, rel = (int(v) for v in R[i])
            own_a = int(S_pn[i, row, node_slot, ADDED])
            own_t = int(S_pn[i, row, node_slot, TAKEN])
            want = max(rel, 0) * max(count, 0)
            d_rel = min(want, max(own_t - own_a, 0))
            inflight = int(S_pn[i, row, :, TAKEN].sum()) - (
                int(S_pn[i, row, :, ADDED].sum()) + d_rel
            )
            k = 0
            while k < nreq and count > 0 and inflight + count <= limit:
                inflight += count
                k += 1
            exp_adm[i] = k
            exp_rel[i] = d_rel
            exp_pn[i, row, node_slot, ADDED] += d_rel
            exp_pn[i, row, node_slot, TAKEN] += k * count
        i = _first_bad(
            (admitted == exp_adm)
            & (released == exp_rel)
            & _states_eq((out_pn, out_el), (exp_pn, S_el))
        )
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] concurrency kernel diverged from the "
                    f"sequential replay at request {R[i].tolist()}: "
                    f"admitted={int(admitted[i])}/released="
                    f"{int(released[i])} expected {int(exp_adm[i])}/"
                    f"{int(exp_rel[i])} — an uncapped release is a phantom "
                    "release: converged replicas would over-admit forever",
                )
            )

    if "PTP004" in root.obligations:
        i = _first_bad(_states_ge((out_pn, out_el), (S_pn, S_el)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] concurrency kernel shrank a state "
                    f"plane at request {R[i].tolist()}: acquire/release "
                    "lanes are monotone G-counters",
                )
            )
    return findings


def _model_quota_laws(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """Hierarchical-quota laws: PTP002 bit-agreement with a literal
    per-request replay admitting against EVERY level's headroom and
    debiting the whole path (including shared global/tenant rows, where
    the packed scatter accumulates), PTP004 monotonicity. The leaf-only
    admission/debit mutations — the family's CRDT hazard — trip the
    PTP002 comparison."""
    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import TAKEN, LimiterState
    from patrol_tpu.ops.hierquota import QuotaRequest

    findings: List[Finding] = []
    node_slot = 0
    dom = JoinDomain(B=3, N=2, vals=(0, 1, 3))
    pn0, el0 = dom.states(dom.vals)

    reqs = np.array(
        [
            (rg, rt, 2, lg, lt, lu, count, nreq)
            for rg, rt in ((0, 1), (0, 0))  # distinct path + shared row
            for lg in (0, 2, 6)
            for lt in (0, 2, 6)
            for lu in (0, 2, 6)
            for count in (1, 2)
            for nreq in (0, 1, 3)
        ],
        np.int64,
    )

    def one(pn, el, r):
        req = QuotaRequest(
            rows_global=r[0].astype(jnp.int32)[None],
            rows_tenant=r[1].astype(jnp.int32)[None],
            rows_user=r[2].astype(jnp.int32)[None],
            limit_global_nt=r[3][None],
            limit_tenant_nt=r[4][None],
            limit_user_nt=r[5][None],
            count_nt=r[6][None],
            nreq=r[7][None],
        )
        out, res = fn(LimiterState(pn=pn, elapsed=el), req, node_slot)
        return out.pn, out.elapsed, res.admitted[0]

    app = jax.jit(jax.vmap(one))
    S_pn, S_el, R = _grid((pn0, el0), (reqs,))
    out_pn, out_el, admitted = _chunked(app, [S_pn, S_el, R])

    if "PTP002" in root.obligations:
        n = len(S_pn)
        exp_pn = S_pn.copy()
        exp_adm = np.zeros(n, np.int64)
        for i in range(n):
            rg, rt, ru, lg, lt, lu, count, nreq = (int(v) for v in R[i])
            spend = [int(S_pn[i, r, :, TAKEN].sum()) for r in (rg, rt, ru)]
            heads = [lg - spend[0], lt - spend[1], lu - spend[2]]
            k = 0
            while k < nreq and count > 0 and min(heads) >= count:
                heads = [h - count for h in heads]
                k += 1
            exp_adm[i] = k
            d = k * count
            for r in (rg, rt, ru):  # shared rows accumulate, like scatter
                exp_pn[i, r, node_slot, TAKEN] += d
        i = _first_bad(
            (admitted == exp_adm)
            & _states_eq((out_pn, out_el), (exp_pn, S_el))
        )
        if i is not None:
            findings.append(
                Finding(
                    "PTP002",
                    *site,
                    f"[{root.name}] quota kernel diverged from the "
                    f"per-level replay at request {R[i].tolist()}: "
                    f"admitted={int(admitted[i])} expected "
                    f"{int(exp_adm[i])} — a partial (leaf-only) check or "
                    "debit lets tenants overspend irreversibly",
                )
            )

    if "PTP004" in root.obligations:
        i = _first_bad(_states_ge((out_pn, out_el), (S_pn, S_el)))
        if i is not None:
            findings.append(
                Finding(
                    "PTP004",
                    *site,
                    f"[{root.name}] quota kernel shrank a state plane at "
                    f"request {R[i].tolist()}: quota debits are monotone "
                    "G-counter spends",
                )
            )
    return findings


def _model_cert_trailer_roundtrip(
    root: ProveRoot, fn: Callable, site: Tuple[str, int]
) -> List[Finding]:
    """PTP003 for the cert-kernel wire trailers (GCRA / concurrency /
    quota, dispatched on ``root.attr``): decode∘encode identity over a
    value grid, byte-stable re-encode, every single-bit corruption
    rejected (the mod-256 checksum covers all bytes), and the family
    invariant the decoder enforces (conc: released <= acquired)."""
    from patrol_tpu.ops import wire

    findings: List[Finding] = []
    big = wire._INT64_MAX
    kind = root.attr

    if "gcra" in kind:
        decode = wire.decode_gcra_trailer
        vals = [
            wire.GcraTrailer(own_slot=s, tat_ns=v)
            for s in (0, 7, 65535)
            for v in (0, 1, big)
        ]
    elif "conc" in kind:
        decode = wire.decode_conc_trailer
        vals = [
            wire.ConcTrailer(own_slot=s, acquired_nt=a, released_nt=r)
            for s in (0, 65535)
            for a in (0, 5, big)
            for r in (0, 5, big)
            if r <= a
        ]
    else:
        decode = wire.decode_quota_trailer
        vals = [
            wire.QuotaTrailer(
                own_slot=s,
                taken_global_nt=g,
                taken_tenant_nt=t,
                taken_user_nt=u,
            )
            for s in (0, 65535)
            for g in (0, 3, big)
            for t in (0, 3, big)
            for u in (0, 3, big)
        ]

    for t in vals:
        pkt = fn(t)
        back = decode(pkt)
        if back != t:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] decode(encode(x)) != x for {t!r}: "
                    "peers relaying the trailer would fork on the lattice "
                    "coordinate it carries",
                )
            )
            break
        if fn(back) != pkt:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] re-encode of a decoded trailer is not "
                    f"byte-stable for {t!r}",
                )
            )
            break

    pkt = fn(vals[-1])
    for i in range(len(pkt)):
        for bit in (0x01, 0x80):
            mutated = bytearray(pkt)
            mutated[i] ^= bit
            if decode(bytes(mutated)) is not None:
                findings.append(
                    Finding(
                        "PTP003",
                        *site,
                        f"[{root.name}] single-bit corruption at byte {i} "
                        "decoded as valid: the trailer checksum must "
                        "reject damaged lattice coordinates",
                    )
                )
                return findings
    if decode(pkt[:-1]) is not None or decode(pkt + b"\x00") is not None:
        findings.append(
            Finding(
                "PTP003",
                *site,
                f"[{root.name}] wrong-length trailer decoded as valid",
            )
        )

    if "conc" in kind:
        phantom = wire.ConcTrailer(own_slot=0, acquired_nt=1, released_nt=2)
        if wire.decode_conc_trailer(wire.encode_conc_trailer(phantom)) is not None:
            findings.append(
                Finding(
                    "PTP003",
                    *site,
                    f"[{root.name}] decoder accepted released > acquired: "
                    "a phantom-release trailer must never merge",
                )
            )
    return findings


_MODELS: Dict[str, Callable] = {
    "dense_join": _model_dense_join,
    "tree_converge": _model_tree_converge,
    "take_monotone": _model_take_monotone,
    "take_n_laws": _model_take_n_laws,
    "take_split_fifo": _model_take_split_fifo,
    "lifecycle_iszero": _model_lifecycle_iszero,
    "scalar_monotone": _model_scalar_monotone,
    "rate_algebra": _model_rate_algebra,
    "wire_roundtrip": _model_wire_roundtrip,
    "delta_roundtrip": _model_delta_roundtrip,
    "raw_ingest": _model_raw_ingest,
    "gcra_laws": _model_gcra_laws,
    "conc_laws": _model_conc_laws,
    "quota_laws": _model_quota_laws,
    "cert_trailer_roundtrip": _model_cert_trailer_roundtrip,
}
# "join_batch:<adapter>" tags dispatch through the adapter registry the
# obligations module fills in (the batch constructors live with the
# kernels, not here).
JOIN_BATCH_ADAPTERS: Dict[str, Callable] = {}


def _run_model(root: ProveRoot, fn: Callable, site: Tuple[str, int]) -> List[Finding]:
    tag = root.model
    if tag is None:
        return []
    if tag.startswith("join_batch:"):
        adapter = JOIN_BATCH_ADAPTERS[tag.split(":", 1)[1]]
        return _model_join_batch(root, fn, adapter, site)
    return _MODELS[tag](root, fn, site)


# ---------------------------------------------------------------------------
# Drivers.


def prove_root(root: ProveRoot, fn: Optional[Callable] = None) -> List[Finding]:
    """Run every declared obligation of one root → findings (unsuppressed)."""
    fn = fn if fn is not None else root.resolve()
    site = _def_site(fn, root)
    findings: List[Finding] = []
    trace: Optional[Trace] = None
    if root.tracer is not None:
        trace = root.tracer(fn)
    if trace is not None and root.structural is not None and "PTP001" in root.obligations:
        findings.extend(structural_check(root, trace, site))
    if trace is not None and "PTP005" in root.obligations:
        findings.extend(dtype_stability_check(root, trace, site))
    findings.extend(_run_model(root, fn, site))
    return findings


def prove_all(roots: Optional[Sequence[ProveRoot]] = None) -> List[Finding]:
    if roots is None:
        from patrol_tpu.ops.obligations import PROVE_ROOTS

        roots = PROVE_ROOTS
    out: List[Finding] = []
    for root in roots:
        out.extend(prove_root(root))
    return sorted(out, key=lambda f: (f.path, f.line, f.check))


# ---------------------------------------------------------------------------
# PTP006 — registration completeness over the engine dispatch graph. Every
# kernel the runtime engines push through jax.jit must appear in PROVE_ROOTS
# (full obligations) or PROVE_EXEMPT (reason on record, in obligations.py) —
# a new kernel cannot land without declared obligations.

ENGINE_DISPATCH_FILES: Tuple[str, ...] = (
    "patrol_tpu/runtime/engine.py",
    "patrol_tpu/runtime/mesh_engine.py",
    "patrol_tpu/parallel/topology.py",
)

_KERNEL_PKG = "patrol_tpu.ops."


def collect_dispatched_kernels(
    sources: Dict[str, str],
    engine_files: Sequence[str] = ENGINE_DISPATCH_FILES,
) -> List[Tuple[str, int, str, str]]:
    """Sweep the engine files for jit-dispatched ops kernels; return
    ``(relpath, line, module, func)`` rows, one per (file, kernel), at
    the kernel's first dispatch line. Shared recognizer: PTP006 checks
    the rows against PROVE_ROOTS/PROVE_EXEMPT, and stage 10's PTD005
    (analysis/dispatch.py) checks them against DISPATCH_SPECS.

    Two dispatch idioms are recognized, matching the engines' shapes:

    * a ``jax.jit(...)`` call — the whole enclosing function (the
      ``@lru_cache`` factory with its local ``step`` closure, or the
      mesh builder assembling ``shard_map(partial(cluster_step, ...))``)
      is treated as the dispatch unit, and every reference out of it
      into a ``patrol_tpu.ops.*`` module-level function counts,
      recursing through same-module helper defs (``cluster_step``);
    * a pre-jitted ``*_jit``-suffixed name resolving into an ops module
      (``zero_rows_jit``, ``delta_ops.delta_fold_jit``) — the kernel is
      the name minus the suffix.

    Batch/request constructors are excluded by construction: only names
    that are module-level ``def``\\ s in the target ops module count (a
    target module absent from ``sources`` keeps its candidates — an
    unresolvable dispatch must not silently pass)."""
    defs_cache: Dict[str, Optional[Set[str]]] = {}

    def kernel_defs(module: str) -> Optional[Set[str]]:
        if module not in defs_cache:
            src = sources.get(module.replace(".", "/") + ".py")
            try:
                defs_cache[module] = (
                    None
                    if src is None
                    else {
                        n.name
                        for n in ast.parse(src).body
                        if isinstance(
                            n, (ast.FunctionDef, ast.AsyncFunctionDef)
                        )
                    }
                )
            except SyntaxError:  # pragma: no cover - repo sources parse
                defs_cache[module] = None
        return defs_cache[module]

    rows: List[Tuple[str, int, str, str]] = []
    for rel in engine_files:
        src = sources.get(rel)
        if src is None:
            continue
        tree = ast.parse(src, filename=rel)

        func_imports: Dict[str, Tuple[str, str]] = {}
        mod_aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.startswith(_KERNEL_PKG):
                        mod_aliases[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    sub = f"{node.module}.{a.name}"
                    if (
                        sub.startswith(_KERNEL_PKG)
                        and sub.replace(".", "/") + ".py" in sources
                    ):
                        mod_aliases[a.asname or a.name] = sub
                    elif node.module.startswith(_KERNEL_PKG):
                        func_imports[a.asname or a.name] = (
                            node.module,
                            a.name,
                        )
        module_defs = {
            n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)
        }

        candidates: Dict[Tuple[str, str], int] = {}

        def note(module: str, name: str, line: int) -> None:
            if name.endswith("_jit"):
                name = name[: -len("_jit")]
            defs = kernel_defs(module)
            if defs is not None and name not in defs:
                return  # a batch/request constructor, not a kernel
            key = (module, name)
            if key not in candidates or line < candidates[key]:
                candidates[key] = line

        def collect(root: ast.AST, visited: Set[ast.AST]) -> None:
            for node in ast.walk(root):
                if isinstance(node, ast.Name) and isinstance(
                    node.ctx, ast.Load
                ):
                    tgt = module_defs.get(node.id)
                    if tgt is not None and tgt not in visited:
                        visited.add(tgt)
                        collect(tgt, visited)
                    elif node.id in func_imports:
                        mod, attr = func_imports[node.id]
                        note(mod, attr, node.lineno)
                elif (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in mod_aliases
                ):
                    note(mod_aliases[node.value.id], node.attr, node.lineno)

        def find_jit_scopes(
            node: ast.AST, enclosing: Optional[ast.AST], acc: List[ast.AST]
        ) -> None:
            for child in ast.iter_child_nodes(node):
                if (
                    isinstance(child, ast.Call)
                    and isinstance(child.func, ast.Attribute)
                    and child.func.attr == "jit"
                    and isinstance(child.func.value, ast.Name)
                    and child.func.value.id == "jax"
                ):
                    acc.append(enclosing if enclosing is not None else child)
                find_jit_scopes(
                    child,
                    child
                    if isinstance(child, ast.FunctionDef)
                    else enclosing,
                    acc,
                )

        scopes: List[ast.AST] = []
        find_jit_scopes(tree, None, scopes)
        seen_scopes: Set[ast.AST] = set()
        for scope in scopes:
            if scope in seen_scopes:
                continue
            seen_scopes.add(scope)
            collect(scope, {scope})

        # Pre-jitted kernels: *_jit names are dispatches wherever they
        # appear, jit scope or not.
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id.endswith("_jit")
                and node.id in func_imports
            ):
                mod, attr = func_imports[node.id]
                note(mod, attr, node.lineno)
            elif (
                isinstance(node, ast.Attribute)
                and node.attr.endswith("_jit")
                and isinstance(node.value, ast.Name)
                and node.value.id in mod_aliases
            ):
                note(mod_aliases[node.value.id], node.attr, node.lineno)

        for (module, name), line in sorted(
            candidates.items(), key=lambda kv: (kv[1], kv[0])
        ):
            rows.append((rel, line, module, name))
    return rows


def registration_findings(
    sources: Dict[str, str],
    registered: Optional[Set[Tuple[str, str]]] = None,
    engine_files: Sequence[str] = ENGINE_DISPATCH_FILES,
) -> List[Finding]:
    """PTP006: sweep the engine files for jit-dispatched kernels
    (:func:`collect_dispatched_kernels`) and flag any (module, func) in
    neither PROVE_ROOTS nor PROVE_EXEMPT."""
    if registered is None:
        from patrol_tpu.ops.obligations import PROVE_EXEMPT, PROVE_ROOTS

        registered = {(r.module, r.attr) for r in PROVE_ROOTS} | set(
            PROVE_EXEMPT
        )
    out: List[Finding] = []
    for rel, line, module, name in collect_dispatched_kernels(
        sources, engine_files
    ):
        if (module, name) not in registered:
            out.append(
                Finding(
                    "PTP006",
                    rel,
                    line,
                    f"jitted kernel {module}.{name} is dispatched here "
                    "but registered in neither PROVE_ROOTS nor "
                    "PROVE_EXEMPT — declare its obligations (or its "
                    "exemption, with the reason) in "
                    "patrol_tpu/ops/obligations.py",
                )
            )
    return sorted(out, key=lambda f: (f.path, f.line, f.check))


def prove_repo(repo_root: str) -> List[Finding]:
    """Prove every registered root + the PTP006 registration-completeness
    sweep, honoring the lint suppression directives in the flagged source
    files (``# patrol-lint: disable=PTP001`` — same machinery, same
    greppability) and sweeping stale PTP suppressions as PTL006."""
    from patrol_tpu.analysis.lint import apply_suppressions, repo_sources

    findings = prove_all() + registration_findings(repo_sources(repo_root))
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return apply_suppressions(findings, repo_root, stale_family="PTP")
