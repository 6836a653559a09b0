"""patrol-dispatch — the dispatch-discipline prover + compile-cache
stability witness (check.sh stage 10, ``scripts/dispatch_repo.py``).

Every recent tentpole (commit coalescing, raw ingest, cert kernels)
added jitted kernels whose performance silently dies if a call site
retraces, breaks donation, or sneaks a host transfer onto the serve
path. Stage 10 proves the XLA dispatch boundary the way stage 9 proves
the lattice algebra: against the declarative per-kernel contracts in
``ops/obligations.py::DISPATCH_SPECS``.

Static half (AST, over the engine dispatch files and the serve graph):

* **PTD001 retrace risk** — a jit dispatch fed a raw python size
  (``len``/``.shape``/``.size`` dataflow that never passed through
  ``engine._pad_size``), an f-string/str()/repr() of shapes, or a
  declared ``pow2`` shape-bucket law with no textually matching
  ``_pad_size`` site left in the engine files (the StagingPool bucket
  registry, machine-readable).
* **PTD002 donation discipline** — (a) drift between a kernel's jit
  binding (``*_jit = partial(jax.jit, ...)``, the engine ``_jit_*``
  factories) and its declared
  ``donate_argnums``/``static_argnames``; (b) use-after-donate at the
  dispatch sites: a donated buffer must be rebound by the dispatch's own
  assignment and must not ride along as a non-donated argument.
* **PTD003 implicit host transfer** — ``.item()``, ``float()/int()/
  bool()`` on device values, ``np.asarray``-family calls on device
  arrays, ``jax.device_get``/``block_until_ready`` in functions
  reachable from the serve roots (feeder, completer, rx ingest,
  cert-kit microbatches, mesh apply, scrape/introspection paths) —
  PTL002's jit-reachability walk generalized to the serve graph.

Dynamic half (the witness, ``run_witness``):

* **PTD004 compile-cache stability** — a deterministic harness warms
  every registered engine hot path (take, merges, commit ring, raw
  ingest, delta fold, gcra/conc/quota, zero_rows, lifecycle probe, the
  fused mesh step), then re-drives each at identical shapes under a jax
  compile counter and the global transfer guard: any post-warmup trace
  or implicit host transfer is a finding carrying the kernel + aval.
* **PTD005 completeness** — every engine-dispatched jitted kernel
  (recognized by the shared ``prove.collect_dispatched_kernels``
  sweep) must be registered in DISPATCH_SPECS, and every spec must
  either name a live witness path or carry a written justified
  absence (PTA005-style); stale/contradictory declarations are
  findings too.

Suppressions ride lint's machinery (``# patrol-lint: disable=PTD003``),
swept for staleness as the ``PTD`` family by the stage driver.
"""

from __future__ import annotations

import ast
import logging
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from patrol_tpu.analysis.lint import (
    SYNC_JAX_FUNCS,
    SYNC_NP_FUNCS,
    Finding,
    Module,
    _FuncIndex,
    repo_sources,
)
from patrol_tpu.analysis.prove import (
    ENGINE_DISPATCH_FILES,
    collect_dispatched_kernels,
)
from patrol_tpu.ops.obligations import DISPATCH_SPECS, DispatchSpec

_ALL = ("PTD001", "PTD002", "PTD003", "PTD004", "PTD005")

# The engine's @lru_cache jit factories → the DispatchSpec kernel each
# one wraps (runtime/engine.py). A factory renamed away from this table
# simply stops resolving a spec — and its jax.jit donation then escapes
# the PTD002 drift check — so the table is itself checked: a _jit_*
# factory in the engine files missing from here is a PTD002 finding.
FACTORY_KERNELS: Dict[str, str] = {
    "_jit_take_packed": "take_n_batch",
    "_jit_merge_folded": "merge_batch_folded",
    "_jit_commit_packed": "commit_blocks",
    "_jit_merge_rows_dense": "merge_rows_dense",
    "_jit_merge_scalar_packed": "merge_scalar_batch",
}

# Instance attributes holding jitted dispatchers (bound in __init__ /
# resize from the topology builders) → their donated argnums. The mesh
# fused step donates the sharded state exactly like the engine paths.
DISPATCHER_ATTRS: Dict[str, Tuple[int, ...]] = {"_step": (0,)}

# The serve graph roots for PTD003: the threads and synchronous entry
# points production traffic rides. Scrape/introspection entries are
# serve surface too — /debug/vars and the audit gauges poll them at
# rates that turn one stray device gather per call into a tick stall.
SERVE_ROOTS: Tuple[Tuple[str, str], ...] = (
    ("patrol_tpu/runtime/engine.py", "DeviceEngine._run_loop"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine._complete_loop"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.ingest_raw_planes"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.ingest_interval"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.ingest_deltas_batch"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.gcra_take"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.conc_acquire"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.quota_take"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.tokens_if_known"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.snapshot"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.snapshot_many"),
    ("patrol_tpu/runtime/engine.py", "DeviceEngine.row_view"),
    ("patrol_tpu/runtime/mesh_engine.py", "MeshEngine._apply"),
)

_SPECS_BY_ATTR: Dict[str, DispatchSpec] = {s.attr: s for s in DISPATCH_SPECS}
_SPECS_BY_KEY: Dict[Tuple[str, str], DispatchSpec] = {
    (s.module, s.attr): s for s in DISPATCH_SPECS
}


def _terminal_name(expr: ast.AST) -> Optional[str]:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def _normalize_donate(node: Optional[ast.AST]) -> Tuple[int, ...]:
    """A donate_argnums keyword value → canonical tuple of ints."""
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, int):
                out.append(el.value)
        return tuple(out)
    return ()


def _normalize_static(node: Optional[ast.AST]) -> Tuple[str, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(
            el.value
            for el in node.elts
            if isinstance(el, ast.Constant) and isinstance(el.value, str)
        )
    return ()


def _is_jax_jit(expr: ast.AST) -> bool:
    return (
        isinstance(expr, ast.Attribute)
        and expr.attr == "jit"
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "jax"
    )


def _jit_call_decl(
    call: ast.Call,
) -> Optional[Tuple[Tuple[int, ...], Tuple[str, ...]]]:
    """``jax.jit(...)`` / ``partial(jax.jit, ...)`` call → its declared
    (donate_argnums, static_argnames), or None if not a jit binding."""
    is_partial = (
        isinstance(call.func, ast.Name) and call.func.id == "partial"
    ) or (
        isinstance(call.func, ast.Attribute) and call.func.attr == "partial"
    )
    if not (
        _is_jax_jit(call.func)
        or (is_partial and any(_is_jax_jit(a) for a in call.args))
    ):
        return None
    donate = static = None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            donate = kw.value
        elif kw.arg == "static_argnames":
            static = kw.value
    return _normalize_donate(donate), _normalize_static(static)


@dataclass
class _Site:
    """One recognized engine dispatch site."""

    call: ast.Call
    kernel: str  # display name (binding / factory / dispatcher attr)
    spec: Optional[DispatchSpec]
    donate: Tuple[int, ...]


def _factory_decls(
    tree: ast.AST,
) -> Dict[str, Tuple[int, Tuple[int, ...], Tuple[str, ...]]]:
    """Module-level ``_jit_*`` factory name → (lineno, donate, static)
    of the ``jax.jit(...)`` call it returns."""
    out: Dict[str, Tuple[int, Tuple[int, ...], Tuple[str, ...]]] = {}
    for node in tree.body if hasattr(tree, "body") else []:
        if not (
            isinstance(node, ast.FunctionDef)
            and node.name.startswith("_jit_")
        ):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                decl = _jit_call_decl(sub)
                if decl is not None:
                    out[node.name] = (sub.lineno, decl[0], decl[1])
                    break
    return out


def dispatch_sites(m: Module) -> List[_Site]:
    """Every recognized dispatch site in one engine module: pre-jitted
    ``*_jit`` names/attrs, ``_jit_*`` factory double-calls, declared
    dispatcher attributes (``self._step``)."""
    fdecls = _factory_decls(m.tree)
    sites: List[_Site] = []
    for node in ast.walk(m.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        tname = _terminal_name(f)
        if tname is not None and tname.endswith("_jit"):
            attr = tname[: -len("_jit")]
            spec = _SPECS_BY_ATTR.get(attr)
            donate = spec.donate_argnums if spec else (0,)
            sites.append(_Site(node, tname, spec, donate))
        elif isinstance(f, ast.Call):
            inner = _terminal_name(f.func)
            if inner is not None and inner.startswith("_jit_"):
                spec = _SPECS_BY_ATTR.get(FACTORY_KERNELS.get(inner, ""))
                decl = fdecls.get(inner)
                donate = (
                    spec.donate_argnums
                    if spec
                    else (decl[1] if decl else (0,))
                )
                sites.append(_Site(node, inner, spec, donate))
        elif (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Name)
            and f.value.id == "self"
            and f.attr in DISPATCHER_ATTRS
        ):
            sites.append(
                _Site(node, f"self.{f.attr}", None, DISPATCHER_ATTRS[f.attr])
            )
    return sites


# ---------------------------------------------------------------------------
# PTD001 — retrace risk.


def _owner_funcs(tree: ast.AST) -> Dict[int, ast.AST]:
    """id(node) → the INNERMOST enclosing function def (or the module)."""
    owners: Dict[int, ast.AST] = {}

    def visit(node: ast.AST, owner: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            nxt = (
                child
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                else owner
            )
            owners[id(child)] = nxt if nxt is not child else child
            visit(child, nxt)

    owners[id(tree)] = tree
    visit(tree, tree)
    return owners


# Array constructors whose leading argument is a SHAPE: the vector by
# which a raw python size becomes a fresh aval at the dispatch boundary.
_SHAPE_CONSTRUCTORS = {
    "zeros",
    "ones",
    "full",
    "empty",
    "arange",
    "tile",
    "repeat",
    "reshape",
    "broadcast_to",
    "lease",
}
# Wrappers that preserve the (possibly tainted) shape of what they wrap.
_SHAPE_WRAPPERS = {
    "asarray",
    "ascontiguousarray",
    "array",
    "device_put",
    "copy",
    "astype",
}


def _is_size_expr(expr: ast.AST, scalar_t: Set[str]) -> bool:
    """A pure scalar-size expression: ``len``/``.shape``/``.size`` reads
    and arithmetic over them (or over already size-tainted names).
    ``_pad_size(...)`` cleanses; any other call is an opaque boundary —
    taint here is SHAPE-level, a gathered value like ``kept[0]`` is not
    a size."""
    if isinstance(expr, ast.Call):
        tname = _terminal_name(expr.func)
        if tname == "_pad_size":
            return False  # bucketed
        if tname == "len":
            return True
        if tname in ("int", "max", "min", "abs"):
            return any(_is_size_expr(a, scalar_t) for a in expr.args)
        return False
    if isinstance(expr, ast.Attribute):
        return expr.attr in ("shape", "size")
    if isinstance(expr, ast.Name):
        return expr.id in scalar_t
    if isinstance(
        expr,
        (
            ast.BinOp,
            ast.UnaryOp,
            ast.IfExp,
            ast.Subscript,
            ast.Tuple,
            ast.Compare,
            ast.BoolOp,
            ast.Starred,
        ),
    ):
        return any(
            _is_size_expr(c, scalar_t) for c in ast.iter_child_nodes(expr)
        )
    return False


def _constructor_tainted(expr: ast.AST, scalar_t: Set[str]) -> bool:
    """A shape-constructor call whose shape argument carries a raw size."""
    return (
        isinstance(expr, ast.Call)
        and _terminal_name(expr.func) in _SHAPE_CONSTRUCTORS
        and bool(expr.args)
        and _is_size_expr(expr.args[0], scalar_t)
    )


def _array_src(expr: ast.AST, scalar_t: Set[str], array_t: Set[str]) -> bool:
    """``expr`` yields an array whose shape descends from a raw size."""
    if _constructor_tainted(expr, scalar_t):
        return True
    if isinstance(expr, ast.Name):
        return expr.id in array_t
    if isinstance(expr, ast.Call):
        tname = _terminal_name(expr.func)
        if tname in _SHAPE_WRAPPERS:
            if any(_array_src(a, scalar_t, array_t) for a in expr.args):
                return True
            if isinstance(expr.func, ast.Attribute) and _array_src(
                expr.func.value, scalar_t, array_t
            ):
                return True  # x.astype(...) / x.copy() methods
    return False


def _retrace_arg(
    expr: ast.AST, scalar_t: Set[str], array_t: Set[str]
) -> bool:
    """A dispatch argument whose aval varies with a raw python size: a
    shape-tainted array, a raw-shape constructor inline, or a bare size
    scalar (retraces per value when the argname is static)."""
    if isinstance(expr, ast.Call) and _terminal_name(expr.func) == "_pad_size":
        return False
    if _constructor_tainted(expr, scalar_t):
        return True
    if isinstance(expr, ast.Name):
        return expr.id in array_t or expr.id in scalar_t
    if isinstance(expr, ast.Call) and _terminal_name(expr.func) == "len":
        return True
    if isinstance(expr, ast.Attribute) and expr.attr in ("shape", "size"):
        return True
    return any(
        _retrace_arg(c, scalar_t, array_t)
        for c in ast.iter_child_nodes(expr)
    )


def _string_shape_in(expr: ast.AST) -> Optional[str]:
    """An f-string / str() / repr() / .format() in a dispatch argument:
    hashable-python-scalar bait that retraces per distinct value."""
    for n in ast.walk(expr):
        if isinstance(n, ast.JoinedStr):
            return "f-string"
        if isinstance(n, ast.Call):
            tname = _terminal_name(n.func)
            if tname in ("str", "repr", "format"):
                return f"{tname}()"
    return None


def _func_taint(fn: ast.AST) -> Tuple[Set[str], Set[str]]:
    """Fixpoint (scalar-size taint, shape-tainted arrays) over the simple
    assignments of one function body (nested defs included — closures
    read outer names)."""
    assigns: List[Tuple[List[str], ast.AST]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            names: List[str] = []
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.append(t.id)
                elif isinstance(t, ast.Tuple):
                    names.extend(
                        el.id for el in t.elts if isinstance(el, ast.Name)
                    )
            if names:
                assigns.append((names, node.value))
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            if isinstance(node.target, ast.Name):
                assigns.append(([node.target.id], node.value))
    scalar_t: Set[str] = set()
    array_t: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for names, value in assigns:
            if _is_size_expr(value, scalar_t):
                for n in names:
                    if n not in scalar_t:
                        scalar_t.add(n)
                        changed = True
            if _array_src(value, scalar_t, array_t):
                for n in names:
                    if n not in array_t:
                        array_t.add(n)
                        changed = True
    return scalar_t, array_t


def check_retrace(mods: Sequence[Module]) -> List[Finding]:
    """PTD001 over the engine dispatch files."""
    out: List[Finding] = []
    engine_mods = [m for m in mods if m.relpath in ENGINE_DISPATCH_FILES]
    pad_laws: Set[Tuple[str, str]] = set()
    for m in engine_mods:
        owners = _owner_funcs(m.tree)
        taint_cache: Dict[int, Tuple[Set[str], Set[str]]] = {}
        for site in dispatch_sites(m):
            line = site.call.lineno
            fn = owners.get(id(site.call), m.tree)
            if id(fn) not in taint_cache:
                taint_cache[id(fn)] = _func_taint(fn)
            scalar_t, array_t = taint_cache[id(fn)]
            fixed = site.spec is not None and site.spec.buckets == "fixed"
            args = list(site.call.args) + [
                kw.value for kw in site.call.keywords
            ]
            for arg in args:
                sdesc = _string_shape_in(arg)
                if sdesc is not None and not m.suppressed("PTD001", line):
                    out.append(
                        Finding(
                            "PTD001",
                            m.relpath,
                            line,
                            f"{sdesc} in an argument of jit dispatch "
                            f"{site.kernel} — hashable python bait that "
                            "retraces per distinct value",
                        )
                    )
                    continue
                if fixed:
                    continue  # geometry pinned by the spec's fixed bucket
                if _retrace_arg(arg, scalar_t, array_t) and not m.suppressed(
                    "PTD001", line
                ):
                    out.append(
                        Finding(
                            "PTD001",
                            m.relpath,
                            line,
                            f"jit dispatch {site.kernel} fed a raw python "
                            f"size ({ast.unparse(arg)[:60]}) that never "
                            "passed through _pad_size — every distinct "
                            "batch size compiles a fresh variant",
                        )
                    )
        # Collect the file's _pad_size sites, normalized to (lo, hi).
        for node in ast.walk(m.tree):
            if (
                isinstance(node, ast.Call)
                and _terminal_name(node.func) == "_pad_size"
                and node.args
            ):
                lo, hi = "8", "MAX_MERGE_ROWS"
                pos = [ast.unparse(a) for a in node.args[1:3]]
                if len(pos) >= 1:
                    lo = pos[0]
                if len(pos) >= 2:
                    hi = pos[1]
                for kw in node.keywords:
                    if kw.arg == "lo":
                        lo = ast.unparse(kw.value)
                    elif kw.arg == "hi":
                        hi = ast.unparse(kw.value)
                pad_laws.add((lo, hi))
    # Bucket-law drift: every declared pow2 law must keep a matching
    # _pad_size site in the engine files.
    if engine_mods:
        anchor = engine_mods[0].relpath
        for spec in DISPATCH_SPECS:
            if spec.buckets != "pow2":
                continue
            if (spec.bucket_lo, spec.bucket_hi) not in pad_laws:
                out.append(
                    Finding(
                        "PTD001",
                        anchor,
                        1,
                        f"declared shape-bucket law of {spec.name} "
                        f"(_pad_size lo={spec.bucket_lo}, "
                        f"hi={spec.bucket_hi}) has no matching _pad_size "
                        "site left in the engine files — the padding was "
                        "dropped or the clamp drifted from the registry",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# PTD002 — donation discipline.


def _binding_decls(
    m: Module,
) -> List[Tuple[int, str, Tuple[int, ...], Tuple[str, ...]]]:
    """(line, kernel attr, donate, static) for every recognized jit
    binding in one ops module: ``X_jit = partial(jax.jit, ...)(X)``
    assignments and ``@partial(jax.jit, ...)`` decorated defs."""
    out: List[Tuple[int, str, Tuple[int, ...], Tuple[str, ...]]] = []
    for node in ast.walk(m.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            tgt = node.targets[0]
            if not (isinstance(tgt, ast.Name) and tgt.id.endswith("_jit")):
                continue
            inner = node.value.func
            if isinstance(inner, ast.Call):
                decl = _jit_call_decl(inner)
                if decl is not None:
                    out.append(
                        (node.lineno, tgt.id[: -len("_jit")], *decl)
                    )
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    decl = _jit_call_decl(dec)
                    if decl is not None:
                        out.append((node.lineno, node.name, *decl))
    return out


def _flat_targets(stmt: ast.Assign) -> List[str]:
    out: List[str] = []
    for t in stmt.targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            out.extend(ast.unparse(el) for el in t.elts)
        else:
            out.append(ast.unparse(t))
    return out


def check_donation(mods: Sequence[Module]) -> List[Finding]:
    """PTD002: binding drift against DISPATCH_SPECS + use-after-donate
    dataflow at the engine dispatch sites."""
    out: List[Finding] = []
    # (a) declaration drift — ops bindings and decorators.
    for m in mods:
        if not m.relpath.startswith("patrol_tpu/ops/"):
            continue
        for line, attr, donate, static in _binding_decls(m):
            spec = _SPECS_BY_ATTR.get(attr)
            if spec is None:
                continue
            if donate != spec.donate_argnums or static != spec.static_argnames:
                if not m.suppressed("PTD002", line):
                    out.append(
                        Finding(
                            "PTD002",
                            m.relpath,
                            line,
                            f"jit binding of {attr} declares donate="
                            f"{donate} static={static}, but DISPATCH_SPECS"
                            f" registers donate={spec.donate_argnums} "
                            f"static={spec.static_argnames} — fix the "
                            "binding or re-certify the registry entry",
                        )
                    )
    engine_mods = [m for m in mods if m.relpath in ENGINE_DISPATCH_FILES]
    for m in engine_mods:
        # (a') engine factory drift.
        fdecls = _factory_decls(m.tree)
        for fname, (line, donate, static) in sorted(fdecls.items()):
            kernel = FACTORY_KERNELS.get(fname)
            if kernel is None:
                if not m.suppressed("PTD002", line):
                    out.append(
                        Finding(
                            "PTD002",
                            m.relpath,
                            line,
                            f"jit factory {fname} is not mapped in "
                            "analysis/dispatch.py::FACTORY_KERNELS — its "
                            "donation contract escapes the drift check",
                        )
                    )
                continue
            spec = _SPECS_BY_ATTR.get(kernel)
            if spec is not None and donate != spec.donate_argnums:
                if not m.suppressed("PTD002", line):
                    out.append(
                        Finding(
                            "PTD002",
                            m.relpath,
                            line,
                            f"jit factory {fname} declares donate={donate}"
                            f" but DISPATCH_SPECS registers {kernel} with "
                            f"donate={spec.donate_argnums}",
                        )
                    )
        # (b) use-after-donate at the dispatch sites.
        parents: Dict[int, ast.AST] = {}
        for node in ast.walk(m.tree):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
        for site in dispatch_sites(m):
            if not site.donate:
                continue
            line = site.call.lineno
            donated = [
                site.call.args[i]
                for i in site.donate
                if i < len(site.call.args)
            ]
            rest = [
                ast.unparse(a)
                for j, a in enumerate(site.call.args)
                if j not in site.donate
            ]
            stmt = parents.get(id(site.call))
            targets = (
                _flat_targets(stmt)
                if isinstance(stmt, ast.Assign) and stmt.value is site.call
                else []
            )
            for d in donated:
                dsrc = ast.unparse(d)
                if not isinstance(d, (ast.Name, ast.Attribute)):
                    if not m.suppressed("PTD002", line):
                        out.append(
                            Finding(
                                "PTD002",
                                m.relpath,
                                line,
                                f"dispatch {site.kernel} donates the "
                                f"anonymous expression {dsrc[:60]} — the "
                                "deleted buffer cannot be rebound, any "
                                "later read hits a donated array",
                            )
                        )
                    continue
                if dsrc in rest and not m.suppressed("PTD002", line):
                    out.append(
                        Finding(
                            "PTD002",
                            m.relpath,
                            line,
                            f"dispatch {site.kernel} passes donated "
                            f"buffer {dsrc} again as a non-donated "
                            "argument — XLA may alias the output over "
                            "the live input",
                        )
                    )
                if dsrc not in targets and not m.suppressed(
                    "PTD002", line
                ):
                    out.append(
                        Finding(
                            "PTD002",
                            m.relpath,
                            line,
                            f"dispatch {site.kernel} donates {dsrc} but "
                            "the result does not rebind it in the same "
                            "assignment — the stale handle outlives its "
                            "donated buffer (use-after-donate)",
                        )
                    )
    return out


# ---------------------------------------------------------------------------
# PTD003 — implicit host transfers on the serve graph.


def _device_taint_names(fn: ast.AST) -> Set[str]:
    """Names in one function bound from dispatch results or device
    reads (``*_jit`` calls, ``_jit_*`` factories, ``self._step``, the
    bare ops-level ``read_rows``). ``self.read_rows`` is NOT a device
    source — the engine method returns host numpy; the transfer inside
    it is the seam this check flags instead."""
    tainted: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign):
            continue
        v = node.value
        hit = False
        if isinstance(v, ast.Call):
            tname = _terminal_name(v.func)
            if tname is not None and tname.endswith("_jit"):
                hit = True
            elif isinstance(v.func, ast.Name) and v.func.id == "read_rows":
                hit = True
            elif isinstance(v.func, ast.Call):
                inner = _terminal_name(v.func.func)
                hit = inner is not None and inner.startswith("_jit_")
            elif (
                isinstance(v.func, ast.Attribute)
                and isinstance(v.func.value, ast.Name)
                and v.func.value.id == "self"
                and v.func.attr in DISPATCHER_ATTRS
            ):
                hit = True
        if hit:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    tainted.add(t.id)
                elif isinstance(t, ast.Tuple):
                    tainted.update(
                        el.id for el in t.elts if isinstance(el, ast.Name)
                    )
    return tainted


def _device_tainted(expr: ast.AST, dnames: Set[str]) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and n.id in dnames:
            return True
        if isinstance(n, ast.Attribute) and n.attr == "state":
            return True
    return False


def check_transfers(mods: Sequence[Module]) -> List[Finding]:
    """PTD003: walk the serve graph from SERVE_ROOTS and flag implicit
    host transfers on device values."""
    index = _FuncIndex(list(mods))
    mod_by_path = {m.relpath: m for m in mods}
    np_aliases: Dict[str, Set[str]] = {}
    jax_aliases: Dict[str, Set[str]] = {}
    for m in mods:
        nps: Set[str] = set()
        jaxs: Set[str] = set()
        for node in ast.walk(m.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "numpy":
                        nps.add(a.asname or a.name)
                    elif a.name == "jax":
                        jaxs.add(a.asname or a.name)
        np_aliases[m.relpath] = nps
        jax_aliases[m.relpath] = jaxs

    seen: Set[Tuple[str, str]] = set()
    reach_from: Dict[Tuple[str, str], Tuple[str, str]] = {}
    frontier = [r for r in SERVE_ROOTS if r in index.funcs]
    while frontier:
        key = frontier.pop()
        if key in seen:
            continue
        seen.add(key)
        fn = index.funcs[key]
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                target = index.resolve(key[0], node, caller=key)
                if target and target in index.funcs and target not in seen:
                    reach_from.setdefault(target, key)
                    frontier.append(target)

    out: List[Finding] = []
    for relpath, name in sorted(seen):
        if not (
            relpath.startswith("patrol_tpu/runtime/")
            or relpath.startswith("patrol_tpu/net/")
            or relpath.startswith("patrol_tpu/parallel/")
        ):
            continue
        m = mod_by_path[relpath]
        fn = index.funcs[(relpath, name)]
        dnames = _device_taint_names(fn)
        via = (
            ""
            if (relpath, name) in SERVE_ROOTS
            else f" (reachable from the serve graph via "
            f"{reach_from.get((relpath, name), ('?', '?'))[1]})"
        )
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            hit = None
            if isinstance(f, ast.Attribute):
                if f.attr == "item":
                    hit = ".item()"
                elif isinstance(f.value, ast.Name):
                    if (
                        f.value.id in np_aliases[relpath]
                        and f.attr in SYNC_NP_FUNCS
                        and node.args
                        and _device_tainted(node.args[0], dnames)
                    ):
                        hit = f"{f.value.id}.{f.attr}() on a device value"
                    elif (
                        f.value.id in jax_aliases[relpath]
                        and f.attr in SYNC_JAX_FUNCS
                    ):
                        hit = f"{f.value.id}.{f.attr}()"
            elif (
                isinstance(f, ast.Name)
                and f.id in ("float", "int", "bool")
                and node.args
                and _device_tainted(node.args[0], dnames)
            ):
                hit = f"{f.id}() on a device value"
            if hit and not m.suppressed("PTD003", node.lineno):
                out.append(
                    Finding(
                        "PTD003",
                        relpath,
                        node.lineno,
                        f"implicit host transfer {hit} inside {name}(), "
                        f"on the serve path{via} — a forced device sync "
                        "per call on the hot path",
                    )
                )
    return out


# ---------------------------------------------------------------------------
# PTD005 — completeness of the registry and the witness table.

WITNESS_PATHS: Tuple[str, ...] = (
    "take",
    "take_n",
    "merge_folded",
    "commit_blocks",
    "merge_rows_dense",
    "merge_scalar",
    "zero_rows",
    "lifecycle_probe",
    "gcra",
    "conc",
    "quota",
    "delta_fold",
    "raw_ingest",
    "read_rows",
    "mesh_step",
)


def check_completeness(sources: Dict[str, str]) -> List[Finding]:
    """PTD005: every dispatched kernel registered; every spec either
    witnessed or justified-absent; declarations internally consistent."""
    out: List[Finding] = []
    for rel, line, module, name in collect_dispatched_kernels(sources):
        if (module, name) not in _SPECS_BY_KEY:
            out.append(
                Finding(
                    "PTD005",
                    rel,
                    line,
                    f"jitted kernel {module}.{name} is dispatched here "
                    "but has no DISPATCH_SPECS record — declare its "
                    "dispatch contract (donation, shape buckets, witness "
                    "path) in patrol_tpu/ops/obligations.py",
                )
            )
    reg = "patrol_tpu/ops/obligations.py"
    for spec in DISPATCH_SPECS:
        if spec.witness and spec.witness_absent:
            out.append(
                Finding(
                    "PTD005",
                    reg,
                    1,
                    f"DISPATCH_SPECS[{spec.name}] declares BOTH a witness "
                    "path and a justified absence — stale justification",
                )
            )
        elif not spec.witness and not spec.witness_absent:
            out.append(
                Finding(
                    "PTD005",
                    reg,
                    1,
                    f"DISPATCH_SPECS[{spec.name}] has neither a witness "
                    "path nor a written justified absence — every "
                    "registered kernel is either re-driven post-warmup "
                    "or its absence is argued on record",
                )
            )
        if spec.witness and spec.witness not in WITNESS_PATHS:
            out.append(
                Finding(
                    "PTD005",
                    reg,
                    1,
                    f"DISPATCH_SPECS[{spec.name}] names witness path "
                    f"'{spec.witness}' which analysis/dispatch.py does "
                    "not implement (WITNESS_PATHS)",
                )
            )
    return sorted(out, key=lambda f: (f.path, f.line, f.check))


# ---------------------------------------------------------------------------
# The static aggregate.


def check_sources(
    sources: Dict[str, str],
    used_out: Optional[Set[Tuple[str, int, str]]] = None,
) -> List[Finding]:
    """The static half (PTD001/PTD002/PTD003/PTD005) over a source map;
    used both by the repo driver and the seeded-mutation fixtures.
    ``used_out`` collects the (path, line, token) suppressions the
    checks honored inline, for the PTL006 stale sweep downstream."""
    mods = [Module(rel, src) for rel, src in sorted(sources.items())]
    findings = (
        check_retrace(mods)
        + check_donation(mods)
        + check_transfers(mods)
        + check_completeness(sources)
    )
    if used_out is not None:
        for m in mods:
            used_out.update((m.relpath, ln, tok) for ln, tok in m.used)
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return findings


def check_repo(
    repo_root: str,
    used_out: Optional[Set[Tuple[str, int, str]]] = None,
) -> List[Finding]:
    return check_sources(repo_sources(repo_root), used_out=used_out)


# ---------------------------------------------------------------------------
# The dynamic witness (PTD004): warm every registered hot path, then
# re-drive at identical shapes under a compile counter + transfer guard.


@dataclass
class WitnessReport:
    findings: List[Finding]
    retraces_after_warmup: int
    jit_cache_entries: int
    paths: Tuple[str, ...]
    compiles: Tuple[str, ...]  # post-warmup "kernel with avals" records


class _CompileLog(logging.Handler):
    """Captures jax's per-compile DEBUG records ("Compiling <name> with
    global shapes and types [ShapedArray(...)]") — kernel + aval, no
    global flags flipped."""

    LOGGERS = ("jax._src.dispatch", "jax._src.interpreters.pxla")

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.records: List[str] = []
        self._saved: List[Tuple[logging.Logger, int]] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if "Compiling" in msg:
            self.records.append(" ".join(msg.split())[:240])

    def __enter__(self) -> "_CompileLog":
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._saved.append((lg, lg.level, lg.propagate))
            lg.setLevel(logging.DEBUG)
            lg.propagate = False  # keep DEBUG records out of stderr
            lg.addHandler(self)
        return self

    def __exit__(self, *exc) -> None:
        for lg, lvl, prop in self._saved:
            lg.removeHandler(self)
            lg.setLevel(lvl)
            lg.propagate = prop
        self._saved.clear()


def _witness_engine():
    from patrol_tpu.models.limiter import NANO, LimiterConfig
    from patrol_tpu.runtime.engine import DeviceEngine

    # Four lanes, so one bucket can fill a row-dense window, and enough
    # rows that a drain can fold past one commit block.
    cfg = LimiterConfig(buckets=4096, nodes=4)
    return DeviceEngine(cfg, node_slot=0, clock=lambda: NANO), cfg


def _witness_drives(eng, cfg):
    """path name → zero-arg drive closure, deterministic inputs, fixed
    shapes; each runs once as the warm leg and once under the guard."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from patrol_tpu.models.limiter import NANO, init_state
    from patrol_tpu.ops import lifecycle as lifecycle_ops
    from patrol_tpu.ops import wire
    from patrol_tpu.ops.rate import Rate
    from patrol_tpu.runtime import engine as engine_mod
    from patrol_tpu.utils import profiling

    rate = Rate(freq=100, per_ns=3600 * NANO)
    names = [f"wit{i}" for i in range(8)]

    def take():
        for i, nm in enumerate(names):
            eng.take(nm, rate, 1, now_ns=NANO + i)

    def merge_folded():
        eng.ingest_deltas_batch(
            names,
            [1] * 8,
            [-1] * 8,
            [-1] * 8,
            [NANO] * 8,
            caps_nt=[-1] * 8,
            lane_added_nt=[100 + i for i in range(8)],
            lane_taken_nt=[10 + i for i in range(8)],
        )
        assert eng.flush(timeout=30)

    def merge_scalar():
        eng.ingest_deltas_batch(
            names,
            [1] * 8,
            [50 + i for i in range(8)],
            [5 + i for i in range(8)],
            [NANO] * 8,
            caps_nt=[1000] * 8,
        )
        assert eng.flush(timeout=30)

    def delta_fold():
        eng.ingest_interval(
            names,
            [1] * 8,
            [1000] * 8,
            [200 + i for i in range(8)],
            [20 + i for i in range(8)],
            [NANO] * 8,
        )
        assert eng.flush(timeout=30)

    def raw_ingest():
        row = 1024
        ents = [
            wire.DeltaEntry(nm, 1, 1000, 300 + i, 30 + i, NANO)
            for i, nm in enumerate(names)
        ]
        data, n = wire.encode_delta_packet(1, 7, [], ents, max_size=row)
        assert n == len(ents)
        planes = np.zeros((2, row), np.uint8)
        planes[0, : len(data)] = np.frombuffer(data, np.uint8)
        lengths = np.array([len(data), 0], np.int32)
        eng.ingest_raw_planes(planes.copy(), lengths)
        assert eng.flush(timeout=30)

    def zero_rows():
        eng.take("victim", rate, 1, now_ns=NANO)
        assert eng.release_bucket("victim", timeout=30)

    def lifecycle_probe():
        lifecycle_ops.lifecycle_probe_jit(
            eng.state,
            lifecycle_ops.LifecycleProbe(
                rows=jnp.zeros(8, jnp.int32),
                now_ns=jnp.zeros(8, jnp.int64),
                per_ns=jnp.zeros(8, jnp.int64),
                cap_base_nt=jnp.zeros(8, jnp.int64),
                created_ns=jnp.zeros(8, jnp.int64),
            ),
            eng.node_slot,
        )

    def gcra():
        eng.gcra_take(
            np.arange(4, dtype=np.int32),
            np.full(4, NANO, np.int64),
            np.full(4, 1000, np.int64),
            np.full(4, 4000, np.int64),
            np.full(4, 1, np.int64),
        )

    def conc():
        eng.conc_acquire(
            np.arange(4, dtype=np.int32),
            np.full(4, 10, np.int64),
            np.full(4, 1, np.int64),
            np.full(4, 1, np.int64),
            np.zeros(4, np.int64),
        )

    def quota():
        eng.quota_take(
            np.zeros(4, np.int32),
            np.full(4, 1, np.int32),
            np.arange(2, 6, dtype=np.int32),
            np.full(4, 1 << 20, np.int64),
            np.full(4, 1 << 16, np.int64),
            np.full(4, 1 << 10, np.int64),
            np.full(4, 1, np.int64),
            np.full(4, 1, np.int64),
        )

    def read_rows():
        eng.read_rows(np.zeros(4, np.int32))

    def merge_rows_dense():
        # One bucket on every lane: the tick folds it into a row window.
        eng.ingest_deltas_batch(
            ["dense"] * cfg.nodes,
            list(range(cfg.nodes)),
            [-1] * cfg.nodes,
            [-1] * cfg.nodes,
            [NANO] * cfg.nodes,
            caps_nt=[-1] * cfg.nodes,
            lane_added_nt=[100 + i for i in range(cfg.nodes)],
            lane_taken_nt=[10 + i for i in range(cfg.nodes)],
        )
        assert eng.flush(timeout=30)

    def commit_blocks():
        # A drain whose fold spans two blocks, through the engine's own
        # tick: it commits as one coalesced ring dispatch.
        n = 2 * engine_mod.MAX_MERGE_ROWS
        before = profiling.COUNTERS.get("commit_dispatches")
        eng._apply(
            engine_mod.DeltaArrays(
                rows=np.arange(n, dtype=np.int64) % cfg.buckets,
                slots=np.arange(n, dtype=np.int64) // cfg.buckets,
                added_nt=np.full(n, 7, np.int64),
                taken_nt=np.full(n, 3, np.int64),
                elapsed_ns=np.full(n, NANO, np.int64),
                scalar=np.zeros(n, bool),
            ),
            (),
        )
        assert profiling.COUNTERS.get("commit_dispatches") == before + 1

    def take_n():
        # The coalesced serving dispatch at a hot-key shape: one packed
        # row per bucket with nreq > 1 (a folded crowd), driven through
        # the SAME lru-cached feeder factory the engine tick uses.
        packed = np.zeros((8, 8), np.int64)
        packed[0] = np.arange(8)  # rows (real bucket rows — takes gather)
        packed[1] = NANO  # now_ns
        packed[2] = 100  # freq
        packed[3] = 3600 * NANO  # per_ns
        packed[4] = NANO  # count_nt
        packed[5] = 3  # nreq: the coalesced crowd size
        packed[6] = 100 * NANO  # cap_base_nt
        st = jax.device_put(init_state(cfg))
        st, out = engine_mod._jit_take_packed(0)(st, jnp.asarray(packed))
        jax.block_until_ready(st.pn)
        jax.block_until_ready(out)

    return {
        "take": take,
        "take_n": take_n,
        "merge_folded": merge_folded,
        "commit_blocks": commit_blocks,
        "merge_rows_dense": merge_rows_dense,
        "merge_scalar": merge_scalar,
        "zero_rows": zero_rows,
        "lifecycle_probe": lifecycle_probe,
        "gcra": gcra,
        "conc": conc,
        "quota": quota,
        "delta_fold": delta_fold,
        "raw_ingest": raw_ingest,
        "read_rows": read_rows,
    }


def _drive_mesh(warm_eng=None):
    """Build (once) and tick the 1-device CPU mesh: the fused
    merge+take step through ``self._step``."""
    import numpy as np

    from patrol_tpu.models.limiter import NANO, LimiterConfig
    from patrol_tpu.runtime.mesh_engine import MeshEngine

    if warm_eng is None:
        warm_eng = MeshEngine(
            LimiterConfig(buckets=256, nodes=2),
            replicas=1,
            node_slot=0,
            clock=lambda: NANO,
        )
        warm_eng.warmup()
    names = [f"mesh{i}" for i in range(8)]
    warm_eng.ingest_deltas_batch(
        names,
        [1] * 8,
        [-1] * 8,
        [-1] * 8,
        [NANO] * 8,
        caps_nt=[-1] * 8,
        lane_added_nt=list(np.arange(8) + 100),
        lane_taken_nt=list(np.arange(8) + 10),
    )
    assert warm_eng.flush(timeout=60)
    return warm_eng


def _jit_cache_entries() -> int:
    """Total compiled-variant count across the pre-jitted ops bindings
    and the engine's lru-cached factories (per-shape cache entries)."""
    from patrol_tpu.ops import commit as commit_mod
    from patrol_tpu.ops import concurrency as conc_mod
    from patrol_tpu.ops import delta as delta_mod
    from patrol_tpu.ops import gcra as gcra_mod
    from patrol_tpu.ops import hierquota as quota_mod
    from patrol_tpu.ops import ingest as ingest_mod
    from patrol_tpu.ops import lifecycle as lifecycle_mod
    from patrol_tpu.ops import merge as merge_mod
    from patrol_tpu.ops import take as take_mod
    from patrol_tpu.runtime import engine as engine_mod

    fns = [
        take_mod.take_batch_jit,
        take_mod.take_n_batch_jit,
        merge_mod.merge_batch_jit,
        merge_mod.merge_scalar_batch_jit,
        merge_mod.merge_dense_jit,
        merge_mod.zero_rows_jit,
        commit_mod.commit_blocks_jit,
        delta_mod.delta_fold_jit,
        ingest_mod.decode_fold_raw_jit,
        lifecycle_mod.lifecycle_probe_jit,
        gcra_mod.gcra_take_batch_jit,
        conc_mod.conc_acquire_batch_jit,
        quota_mod.quota_take_batch_jit,
        engine_mod._jit_take_packed(0),
        engine_mod._jit_merge_folded(),
        engine_mod._jit_commit_packed(),
        engine_mod._jit_merge_rows_dense(),
        engine_mod._jit_merge_scalar_packed(),
    ]
    total = 0
    for fn in fns:
        try:
            total += int(fn._cache_size())
        except Exception:
            pass
    return total


def run_witness(mutate: Optional[str] = None) -> WitnessReport:
    """PTD004: warm every witness path, then re-drive each at identical
    shapes under the compile counter + the global transfer guard. Any
    post-warmup trace or implicit host transfer is a finding carrying
    the path, kernel, and aval. ``mutate="unbucketed_aval"`` adds a
    seeded post-warmup drive at an aval outside the declared buckets
    (the dynamic mutation stage 10 must demonstrably reject)."""
    import jax

    eng, cfg = _witness_engine()
    findings: List[Finding] = []
    compiles: List[str] = []
    anchor = "patrol_tpu/runtime/engine.py"
    mesh = None
    try:
        eng.warmup()
        drives = _witness_drives(eng, cfg)
        for path, drive in drives.items():
            drive()  # warm leg
        mesh = _drive_mesh()  # warm leg (builds + warms the mesh)

        paths = tuple(drives) + ("mesh_step",)
        retraces = 0
        # D2H only: implicit device→host syncs are the serve-path sin.
        # Host→device staging of request scalars/arrays is the designed
        # ingest direction and stays allowed. Global (not the
        # context-manager form): the engine's feeder/completer threads
        # must be covered too, and the context manager is thread-local.
        jax.config.update("jax_transfer_guard_device_to_host", "disallow")
        try:
            with _CompileLog() as clog:
                mark = 0
                for path in paths:
                    try:
                        if path == "mesh_step":
                            _drive_mesh(mesh)
                        else:
                            drives[path]()
                    except Exception as exc:  # transfer guard trips here
                        findings.append(
                            Finding(
                                "PTD004",
                                anchor,
                                1,
                                f"witness path '{path}': unguarded host "
                                f"transfer under jax.transfer_guard — "
                                f"{type(exc).__name__}: {str(exc)[:160]}",
                            )
                        )
                    fresh = clog.records[mark:]
                    mark = len(clog.records)
                    for rec in fresh:
                        retraces += 1
                        compiles.append(f"{path}: {rec}")
                        findings.append(
                            Finding(
                                "PTD004",
                                anchor,
                                1,
                                f"witness path '{path}' retraced after "
                                f"warmup — {rec}",
                            )
                        )
                if mutate == "unbucketed_aval":
                    import numpy as np

                    import jax.numpy as jnp

                    from patrol_tpu.runtime import engine as engine_mod

                    with eng._state_mu:
                        eng.state = engine_mod._jit_merge_scalar_packed()(
                            eng.state, jnp.zeros((5, 9), jnp.int64)
                        )
                        jax.block_until_ready(eng.state.pn)
                    for rec in clog.records[mark:]:
                        retraces += 1
                        compiles.append(f"unbucketed_aval: {rec}")
                        findings.append(
                            Finding(
                                "PTD004",
                                anchor,
                                1,
                                "witness path 'unbucketed_aval': aval "
                                f"outside the declared buckets — {rec}",
                            )
                        )
        finally:
            jax.config.update("jax_transfer_guard_device_to_host", "allow")
        entries = _jit_cache_entries()
    finally:
        if mesh is not None:
            mesh.stop()
        eng.stop()
    return WitnessReport(
        findings=findings,
        retraces_after_warmup=retraces,
        jit_cache_entries=entries,
        paths=paths,
        compiles=tuple(compiles),
    )


# ---------------------------------------------------------------------------
# Seeded mutations: each fixture is the clean baseline with exactly one
# dispatch-discipline defect, and the static stack must reject it with
# the exact registered code. The dynamic mutation rides run_witness.

_FIXTURE_BASELINE = '''\
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial
from patrol_tpu.ops.take import take_batch_jit
from patrol_tpu.ops.merge import merge_batch_jit

MAX_TAKE_ROWS = 4096
MAX_MERGE_ROWS = 8192
MAX_ROW_DENSE = 512


def _pad_size(n, lo=8, hi=MAX_MERGE_ROWS):
    return max(lo, min(n, hi))


def _bucket_ladder(keys, R, n, m):
    a = _pad_size(len(keys), hi=MAX_TAKE_ROWS)
    b = _pad_size(n)
    c = _pad_size(R, lo=8, hi=MAX_ROW_DENSE)
    d = _pad_size(m, lo=8, hi=1 << 20)
    e = _pad_size(m, lo=1, hi=1 << 20)
    return a, b, c, d, e


class Engine:
    def serve(self, keys):
        k = _pad_size(len(keys), hi=MAX_TAKE_ROWS)
        packed = jnp.zeros((8, k), jnp.int64)
        self.state, out = take_batch_jit(self.state, packed, 0)
        return out
'''

_MUT_SNIPPETS: Dict[str, Tuple[str, str, str]] = {
    # name → (expect code, note, appended defect source)
    "shape_varying_call_site": (
        "PTD001",
        "jit dispatch fed a raw len() that skipped _pad_size",
        '''

    def serve_unpadded(self, keys):
        n = len(keys)
        packed = jnp.zeros((8, n), jnp.int64)
        self.state, out = take_batch_jit(self.state, packed, 0)
        return out
''',
    ),
    "donated_buffer_reuse": (
        "PTD002",
        "donated state never rebound by the dispatch result",
        '''

    def commit_leaky(self, packed):
        shadow = merge_batch_jit(self.state, packed)
        return shadow
''',
    ),
    "item_on_serve_path": (
        "PTD003",
        ".item() host sync inside the completer loop",
        '''

class DeviceEngine:
    def _complete_loop(self):
        self.state = merge_batch_jit(self.state, self.packed)
        return self.state.pn[0].item()
''',
    ),
    "unregistered_kernel": (
        "PTD005",
        "a dispatched jitted kernel with no DISPATCH_SPECS record",
        '''

from patrol_tpu.ops.shadow import shadow_fold_jit


class Engine2:
    def fold(self, packed):
        self.state = shadow_fold_jit(self.state, packed)
''',
    ),
}

DISPATCH_MUTATIONS: Dict[str, str] = {
    name: code for name, (code, _, _) in _MUT_SNIPPETS.items()
}
DISPATCH_MUTATIONS["unbucketed_aval"] = "PTD004"


def mutation_findings(name: str) -> List[Finding]:
    """Run the static stack over the seeded fixture for ``name``; the
    dynamic ``unbucketed_aval`` mutation runs the witness instead."""
    if name == "unbucketed_aval":
        return run_witness(mutate="unbucketed_aval").findings
    code, _, snippet = _MUT_SNIPPETS[name]
    sources = {
        "patrol_tpu/runtime/engine.py": _FIXTURE_BASELINE + snippet,
    }
    if name == "unregistered_kernel":
        sources["patrol_tpu/ops/shadow.py"] = (
            "def shadow_fold(state, packed):\n    return state\n"
            "shadow_fold_jit = shadow_fold\n"
        )
    return check_sources(sources)


def clean_fixture_findings() -> List[Finding]:
    """The baseline fixture must pass the static stack clean — the
    both-ways control for the seeded mutations."""
    return check_sources(
        {"patrol_tpu/runtime/engine.py": _FIXTURE_BASELINE}
    )
