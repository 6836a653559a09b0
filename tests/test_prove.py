"""patrol-prove self-tests (PTP001-PTP005).

Every obligation is proven BOTH ways: it fires on a seeded broken kernel
and stays silent on the shipped ones. The mutation test at the bottom is
the gate's reason to exist: monkeypatch `merge_dense`'s max into an add —
the historically-likely refactor mistake — and both prover passes must
reject it. `TestRepoIsProven` is the `pytest -m prove` slice of the
scripts/check.sh stage-4 contract.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from patrol_tpu.analysis import prove
from patrol_tpu.models.limiter import LimiterState
from patrol_tpu.ops import take as take_mod
from patrol_tpu.ops.merge import MergeBatch
from patrol_tpu.ops.obligations import PROVE_ROOTS

pytestmark = pytest.mark.prove

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROOTS = {r.attr: r for r in PROVE_ROOTS}


def codes(findings):
    return sorted({f.check for f in findings})


def scoped(base, *obligations, model="keep"):
    """A copy of a registry root narrowed to specific obligations (so a
    fixture isolates exactly one PTP code)."""
    return dataclasses.replace(
        base,
        obligations=tuple(obligations),
        model=base.model if model == "keep" else model,
    )


# --- seeded broken kernels -------------------------------------------------


def add_merge_dense(a, b):
    """The classic refactor mistake: + where max belongs."""
    return LimiterState(
        pn=a.pn + b.pn, elapsed=jnp.maximum(a.elapsed, b.elapsed)
    )


def set_merge_batch(state, batch):
    """Last-write-wins scatter: order-dependent, non-monotone."""
    pair = jnp.stack([batch.added_nt, batch.taken_nt], axis=-1)
    pn = state.pn.at[batch.rows, batch.slots].set(pair)
    elapsed = state.elapsed.at[batch.rows].max(batch.elapsed_ns)
    return LimiterState(pn=pn, elapsed=elapsed)


def f32_merge_dense(a, b):
    """f32 creeping into the pn planes."""
    pn = jnp.maximum(a.pn.astype(jnp.float32), b.pn.astype(jnp.float32))
    return LimiterState(pn=pn, elapsed=jnp.maximum(a.elapsed, b.elapsed))


def narrowed_merge_dense(a, b):
    """Integer but narrowed: silent truncation at 2^31 nanotokens."""
    return LimiterState(
        pn=jnp.maximum(a.pn, b.pn).astype(jnp.int32),
        elapsed=jnp.maximum(a.elapsed, b.elapsed),
    )


def min_merge_dense(a, b):
    """Commutative, associative, idempotent — and NOT monotone: the one
    lattice property min gets wrong (it is the meet, not the join)."""
    return LimiterState(
        pn=jnp.minimum(a.pn, b.pn), elapsed=jnp.minimum(a.elapsed, b.elapsed)
    )


def first_wins_merge_dense(a, b):
    """Keep a's value wherever nonzero: idempotent but not commutative."""
    pn = jnp.where(a.pn > 0, a.pn, b.pn)
    elapsed = jnp.where(a.elapsed > 0, a.elapsed, b.elapsed)
    return LimiterState(pn=pn, elapsed=elapsed)


def callback_take(state, req, node_slot):
    jax.debug.callback(lambda x: None, req.rows)
    return take_mod.take_batch(state, req, node_slot)


def scan_add_merge_dense(a, b):
    """The add hides inside a lax.scan body: the taint walk's conservative
    control-flow handling (taint every sub-jaxpr input) must still see it."""
    def body(pn, xs):
        return pn + xs, jnp.int64(0)

    pn, _ = jax.lax.scan(body, a.pn, b.pn[None])
    return LimiterState(pn=pn, elapsed=jnp.maximum(a.elapsed, b.elapsed))


def while_add_merge_dense(a, b):
    """Same, through lax.while_loop: one trip whose body accumulates."""
    def cond(c):
        return c[1] < 1

    def body(c):
        return (c[0] + b.pn, c[1] + 1)

    pn, _ = jax.lax.while_loop(cond, body, (a.pn, jnp.int64(0)))
    return LimiterState(pn=pn, elapsed=jnp.maximum(a.elapsed, b.elapsed))


def scan_max_merge_dense(a, b):
    """Control flow whose body stays on the join allowlist: conservative
    must not mean trigger-happy."""
    def body(pn, xs):
        return jnp.maximum(pn, xs), jnp.int64(0)

    pn, _ = jax.lax.scan(body, a.pn, b.pn[None])
    return LimiterState(pn=pn, elapsed=jnp.maximum(a.elapsed, b.elapsed))


def leaky_take(state, req, node_slot):
    """Writes a lane that is not its own (node_slot+1): a correctness
    disaster under PN-sum semantics."""
    out, res = take_mod.take_batch(state, req, node_slot)
    pair = jnp.stack([req.count_nt, req.count_nt], axis=-1)
    pn = out.pn.at[req.rows, node_slot + 1].add(pair)
    return LimiterState(pn=pn, elapsed=out.elapsed), res


# --- PTP001: structural lattice / callback pass ----------------------------


class TestStructuralPass:
    def test_fires_on_add_on_merged_plane(self):
        root = scoped(ROOTS["merge_dense"], "PTP001", model=None)
        f = prove.prove_root(root, fn=add_merge_dense)
        assert codes(f) == ["PTP001"]
        assert "'add'" in f[0].message

    def test_fires_on_overwrite_scatter(self):
        root = scoped(ROOTS["merge_batch"], "PTP001", model=None)
        f = prove.prove_root(root, fn=set_merge_batch)
        assert codes(f) == ["PTP001"]
        assert "scatter" in f[0].message

    def test_fires_on_float_cast_of_state_plane(self):
        root = scoped(ROOTS["merge_dense"], "PTP001", model=None)
        f = prove.prove_root(root, fn=f32_merge_dense)
        assert codes(f) == ["PTP001"]
        assert "float cast" in f[0].message

    def test_fires_on_callback_primitive(self):
        root = scoped(ROOTS["take_batch"], "PTP001", model=None)
        f = prove.prove_root(root, fn=callback_take)
        assert codes(f) == ["PTP001"]
        assert "callback" in f[0].message

    def test_silent_on_shipped_joins(self):
        for attr in ("merge_batch", "merge_batch_folded", "merge_rows_dense",
                     "merge_dense", "read_rows"):
            root = scoped(ROOTS[attr], "PTP001", model=None)
            assert prove.prove_root(root) == [], attr

    def test_take_local_adds_are_not_flagged(self):
        # The delta-side profile: take's scatter-add is the point, not a
        # violation — only callbacks are structural findings there.
        root = scoped(ROOTS["take_batch"], "PTP001", model=None)
        assert prove.prove_root(root) == []

    def test_index_math_is_not_tainted(self):
        # merge_batch's jaxpr contains add/select_n on the *row indices*
        # (negative-index normalization); taint tracking must not confuse
        # index math with state-plane math.
        root = scoped(ROOTS["merge_batch"], "PTP001", model=None)
        assert prove.prove_root(root) == []


class TestConservativeControlFlow:
    """ROADMAP gap closed: PTP001's scan/while handling (taint the whole
    sub-jaxpr) finally has fixtures exercising it — a disallowed primitive
    INSIDE a loop body carrying a state plane must fire, and an
    allowlisted body must not."""

    def test_fires_on_add_inside_scan_body(self):
        root = scoped(ROOTS["merge_dense"], "PTP001", model=None)
        f = prove.prove_root(root, fn=scan_add_merge_dense)
        assert codes(f) == ["PTP001"]
        assert any("'add'" in x.message for x in f)

    def test_fires_on_add_inside_while_body(self):
        root = scoped(ROOTS["merge_dense"], "PTP001", model=None)
        f = prove.prove_root(root, fn=while_add_merge_dense)
        assert codes(f) == ["PTP001"]

    def test_silent_on_max_only_scan_body(self):
        root = scoped(ROOTS["merge_dense"], "PTP001", model=None)
        assert prove.prove_root(root, fn=scan_max_merge_dense) == []


# --- PTP002/PTP003/PTP004: the small-domain model checker ------------------


class TestModelChecker:
    def test_commutativity_fires_on_first_wins_join(self):
        root = scoped(ROOTS["merge_dense"], "PTP002")
        f = prove.prove_root(root, fn=first_wins_merge_dense)
        assert "PTP002" in codes(f)

    def test_commutativity_fires_on_overwrite_scatter(self):
        root = scoped(ROOTS["merge_batch"], "PTP002")
        f = prove.prove_root(root, fn=set_merge_batch)
        assert codes(f) == ["PTP002"]

    def test_idempotence_fires_on_add_join(self):
        root = scoped(ROOTS["merge_dense"], "PTP003")
        f = prove.prove_root(root, fn=add_merge_dense)
        assert codes(f) == ["PTP003"]
        assert "idempotent" in f[0].message

    def test_monotonicity_fires_on_meet_join(self):
        # min commutes, associates, and is idempotent — the model checker
        # must still reject it on monotonicity alone.
        root = scoped(ROOTS["merge_dense"], "PTP004")
        f = prove.prove_root(root, fn=min_merge_dense)
        assert codes(f) == ["PTP004"]

    def test_take_monotonicity_fires_on_foreign_lane_write(self):
        root = scoped(ROOTS["take_batch"], "PTP004")
        f = prove.prove_root(root, fn=leaky_take)
        assert codes(f) == ["PTP004"]
        assert "lane" in f[0].message

    def test_silent_on_shipped_kernels(self):
        for attr in ("merge_batch", "merge_batch_folded", "merge_rows_dense",
                     "merge_dense"):
            root = scoped(ROOTS[attr], "PTP002", "PTP003", "PTP004")
            assert prove.prove_root(root) == [], attr
        assert prove.prove_root(scoped(ROOTS["take_batch"], "PTP004")) == []


# --- PTP005: dtype/shape stability under jit -------------------------------


class TestDtypeStability:
    def test_fires_on_integer_narrowing(self):
        # int32 output is NOT a float leak (PTP001 stays silent) but IS a
        # dtype instability — the two codes separate cleanly.
        root = scoped(ROOTS["merge_dense"], "PTP005", model=None)
        f = prove.prove_root(root, fn=narrowed_merge_dense)
        assert codes(f) == ["PTP005"]

    def test_fires_on_float_output(self):
        root = scoped(ROOTS["merge_dense"], "PTP005", model=None)
        f = prove.prove_root(root, fn=f32_merge_dense)
        assert codes(f) == ["PTP005"]
        assert "float" in f[0].message

    def test_silent_on_shipped_kernels(self):
        for attr in ("merge_batch", "merge_batch_folded", "merge_rows_dense",
                     "merge_dense", "merge_scalar_batch", "read_rows",
                     "take_batch"):
            root = scoped(ROOTS[attr], "PTP005", model=None)
            assert prove.prove_root(root) == [], attr


# --- the mutation gate (ISSUE 3 satellite): max -> add on merge_dense ------


class TestMutationGate:
    def test_max_to_add_mutation_is_rejected_by_both_passes(self, monkeypatch):
        """The historically-likely refactor mistake, end to end: mutate the
        *registered* kernel and run the root exactly as prove_repo would.
        The structural pass must flag the add on the merged plane AND the
        model checker must catch the idempotence break — two independent
        tripwires for the same bug."""
        import patrol_tpu.ops.merge as merge_mod

        monkeypatch.setattr(merge_mod, "merge_dense", add_merge_dense)
        f = prove.prove_root(ROOTS["merge_dense"])  # resolves dynamically
        got = codes(f)
        assert "PTP001" in got, f  # pass 1: structural lattice check
        assert "PTP003" in got, f  # pass 2: small-domain model check

    def test_registry_resolution_is_dynamic(self, monkeypatch):
        # The registry stores (module, attr), not a function object — the
        # gate checks what the engine would actually import.
        import patrol_tpu.ops.merge as merge_mod

        monkeypatch.setattr(merge_mod, "merge_dense", min_merge_dense)
        f = prove.prove_root(ROOTS["merge_dense"])
        assert "PTP004" in codes(f)


# --- suppression + drivers -------------------------------------------------


class TestSuppression:
    def test_ptp_codes_ride_the_lint_directive(self):
        from patrol_tpu.analysis.lint import Module

        mod = Module(
            "patrol_tpu/ops/x.py",
            "a = 1  # patrol-lint: disable=PTP001,PTP004\n",
        )
        assert mod.suppressed("PTP001", 1)
        assert mod.suppressed("PTP004", 1)
        assert not mod.suppressed("PTP002", 1)

    def test_prove_repo_filters_suppressed_findings(self, tmp_path, monkeypatch):
        from patrol_tpu.analysis.lint import Finding

        src = tmp_path / "patrol_tpu" / "ops"
        src.mkdir(parents=True)
        (src / "fake.py").write_text(
            "x = 1\ny = 2  # patrol-lint: disable=PTP001\n"
        )
        crafted = [
            Finding("PTP001", "patrol_tpu/ops/fake.py", 1, "kept"),
            Finding("PTP001", "patrol_tpu/ops/fake.py", 2, "suppressed"),
        ]
        monkeypatch.setattr(prove, "prove_all", lambda roots=None: crafted)
        out = prove.prove_repo(str(tmp_path))
        assert [f.line for f in out] == [1]


class TestRepoIsProven:
    def test_repo_proves_clean(self):
        """The stage-4 contract: zero findings on the shipped kernels."""
        findings = prove.prove_repo(REPO_ROOT)
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_registry_covers_the_kernel_roots(self):
        """Guard against a vacuously-clean prover: the CRDT-critical roots
        must be registered with their full obligation sets."""
        names = {r.name for r in PROVE_ROOTS}
        for required in (
            "ops.merge.merge_batch",
            "ops.merge.merge_dense",
            "parallel.topology.tree_reduce_states",
            "ops.merge.merge_batch_folded",
            "ops.merge.merge_rows_dense",
            "ops.merge.read_rows",
            "ops.take.take_batch",
            "ops.rate",
            "ops.wire.codec",
            "ops.wire.delta_codec",
            "ops.delta.delta_fold",
        ):
            assert required in names, required
        full = set(ROOTS["merge_batch"].obligations)
        assert full == {"PTP001", "PTP002", "PTP003", "PTP004", "PTP005"}

    def test_every_code_is_declared_somewhere(self):
        declared = set()
        for r in PROVE_ROOTS:
            declared.update(r.obligations)
        assert declared == set(prove.ALL_CODES)

    def test_scalar_merge_declares_no_join_algebra(self):
        """merge_scalar_batch is deliberately lossy (deficit attribution):
        the registry must record that design decision by NOT declaring
        commutativity/idempotence for it."""
        obl = set(ROOTS["merge_scalar_batch"].obligations)
        assert "PTP002" not in obl and "PTP003" not in obl
        assert "PTP004" in obl


_UNREGISTERED_DISPATCH = {
    "patrol_tpu/runtime/engine.py": (
        "from functools import lru_cache\n"
        "import jax\n"
        "from patrol_tpu.ops.frob import frob_batch, FrobRequest\n"
        "\n"
        "@lru_cache(maxsize=8)\n"
        "def _jit_frob():\n"
        "    def step(state, packed):\n"
        "        req = FrobRequest(packed)\n"
        "        return frob_batch(state, req)\n"
        "    return jax.jit(step, donate_argnums=0)\n"
    ),
    "patrol_tpu/ops/frob.py": (
        "class FrobRequest:\n"
        "    def __init__(self, packed):\n"
        "        self.packed = packed\n"
        "\n"
        "def frob_batch(state, req):\n"
        "    return state\n"
    ),
}


class TestRegistrationCompleteness:
    """PTP006: the engine dispatch graph may only reach registered (or
    explicitly exempted) kernels — proven both ways on fixtures, plus the
    non-vacuous discovery guard on the real tree."""

    def test_seeded_unregistered_dispatch_is_rejected(self):
        f = prove.registration_findings(_UNREGISTERED_DISPATCH, registered=set())
        assert codes(f) == ["PTP006"]
        assert "patrol_tpu.ops.frob.frob_batch" in f[0].message
        # The request constructor is NOT mistaken for a kernel.
        assert "FrobRequest" not in f[0].message

    def test_registered_dispatch_is_clean(self):
        reg = {("patrol_tpu.ops.frob", "frob_batch")}
        assert prove.registration_findings(_UNREGISTERED_DISPATCH, registered=reg) == []

    def test_exempt_set_counts_as_registered(self):
        from patrol_tpu.ops.obligations import PROVE_EXEMPT

        assert ("patrol_tpu.ops.merge", "zero_rows") in PROVE_EXEMPT

    def test_prejitted_suffix_names_are_dispatches(self):
        srcs = {
            "patrol_tpu/runtime/engine.py": (
                "from patrol_tpu.ops.frob import frob_batch_jit\n"
                "def tick(self, state, rows):\n"
                "    return frob_batch_jit(state, rows)\n"
            ),
            "patrol_tpu/ops/frob.py": (
                "def frob_batch(state, rows):\n    return state\n"
                "frob_batch_jit = frob_batch\n"
            ),
        }
        f = prove.registration_findings(srcs, registered=set())
        assert codes(f) == ["PTP006"]
        assert "patrol_tpu.ops.frob.frob_batch " in f[0].message

    def test_module_alias_dispatch_through_builder_chain(self):
        # The topology idiom: jit(wrapper(partial(module_level_step))).
        srcs = {
            "patrol_tpu/parallel/topology.py": (
                "from functools import partial\n"
                "import jax\n"
                "from patrol_tpu.ops import frob as frob_mod\n"
                "\n"
                "def cluster_step(state, reqs):\n"
                "    return frob_mod.frob_batch(state, reqs)\n"
                "\n"
                "def build(mesh):\n"
                "    fn = partial(cluster_step)\n"
                "    return jax.jit(fn, donate_argnums=0)\n"
            ),
            "patrol_tpu/ops/frob.py": "def frob_batch(state, reqs):\n    return state\n",
        }
        f = prove.registration_findings(srcs, registered=set())
        assert codes(f) == ["PTP006"]

    def test_real_dispatch_graph_is_discovered(self):
        """Guard against a vacuously-clean PTP006: the engines' actual
        kernels must be visible to the sweep."""
        from patrol_tpu.analysis.lint import repo_sources

        f = prove.registration_findings(repo_sources(REPO_ROOT), registered=set())
        found = {m.split(" is dispatched")[0].split()[-1] for m in (x.message for x in f)}
        for kernel in (
            "patrol_tpu.ops.take.take_batch",
            "patrol_tpu.ops.merge.merge_batch",
            "patrol_tpu.ops.merge.merge_batch_folded",
            "patrol_tpu.ops.commit.commit_blocks",
            "patrol_tpu.ops.delta.delta_fold",
            "patrol_tpu.ops.ingest.decode_fold_raw",
            "patrol_tpu.ops.lifecycle.lifecycle_probe",
            "patrol_tpu.ops.merge.zero_rows",
        ):
            assert kernel in found, kernel

    def test_real_dispatch_graph_is_registered(self):
        from patrol_tpu.analysis.lint import repo_sources

        f = prove.registration_findings(repo_sources(REPO_ROOT))
        assert f == [], "\n".join(str(x) for x in f)


def add_delta_fold(state, batch):
    """Seeded wire-v2 rx-fold bug: accumulating an interval instead of
    joining it — duplicated/retransmitted intervals would inflate state."""
    pair = jnp.stack([batch.added_nt, batch.taken_nt], axis=-1)
    pn = state.pn.at[batch.rows, batch.slots].add(pair, mode="drop")
    elapsed = state.elapsed.at[batch.rows].add(batch.elapsed_ns, mode="drop")
    return LimiterState(pn=pn, elapsed=elapsed)


class TestDeltaObligations:
    """The wire-v2 roots: delta_fold carries the FULL join obligation set
    and the interval codec the roundtrip obligation — and both reject
    their seeded mutations (the prover keeps its teeth on the new plane)."""

    def test_delta_fold_proves_clean(self):
        assert prove.prove_root(ROOTS["delta_fold"]) == []

    def test_delta_fold_full_obligations_declared(self):
        assert set(ROOTS["delta_fold"].obligations) == set(prove.ALL_CODES)

    def test_add_delta_fold_rejected_by_model_and_structure(self):
        f = prove.prove_root(ROOTS["delta_fold"], fn=add_delta_fold)
        got = codes(f)
        # Structural taint (add on a merged plane) AND the model checker
        # (idempotence breaks: re-applying an interval moves state).
        assert "PTP001" in got and "PTP003" in got

    def test_delta_codec_proves_clean(self):
        assert prove.prove_root(ROOTS["encode_delta_packet"]) == []

    def test_delta_codec_mutation_rejected(self):
        from patrol_tpu.ops import wire

        def checksum_off_by_one(slot, seq, acks, entries,
                                max_size=wire.DELTA_PACKET_SIZE):
            pkt, n = wire.encode_delta_packet(slot, seq, acks, entries, max_size)
            return pkt[:-1] + bytes([(pkt[-1] + 1) & 0xFF]), n

        f = prove.prove_root(ROOTS["encode_delta_packet"], fn=checksum_off_by_one)
        assert codes(f) == ["PTP003"]


def accept_bad_checksum_ingest(state, planes, lengths, entry_off, rows, hosted):
    """Seeded raw-ingest bug: 'fix up' every plane's checksum before the
    real kernel — corrupted datagrams then decode+fold as if valid, the
    exact replica-fork class the all-or-nothing validation exists for."""
    from patrol_tpu.ops import ingest as ingest_ops

    P, row = planes.shape
    pl = planes.astype(jnp.int32)
    end = jnp.clip(lengths.astype(jnp.int64) - 1, 0, row - 1)
    col = jnp.arange(row)
    body = jnp.where(
        (col[None, :] >= 32) & (col[None, :] < end[:, None]), pl, 0
    )
    ck = (body.sum(axis=1) & 0xFF).astype(planes.dtype)
    planes2 = planes.at[jnp.arange(P), end].set(ck)
    return ingest_ops.decode_fold_raw(
        state, planes2, lengths, entry_off, rows, hosted
    )


def add_fold_ingest(state, planes, lengths, entry_off, rows, hosted):
    """Seeded raw-ingest bug on the fold leg: accumulate instead of join
    — duplicated or retransmitted planes would inflate state."""
    from patrol_tpu.ops import ingest as ingest_ops

    out = ingest_ops._device_decode(planes, lengths, entry_off)
    ok, count, slot, cap, added, taken, elapsed = out
    live = ok[:, None] & (jnp.arange(rows.shape[1])[None, :] < count[:, None])
    fold = live & ~hosted & (slot >= 0) & (slot < state.pn.shape[1])
    frows = jnp.where(fold, rows, ingest_ops.FOLD_PAD_ROW)
    pair = jnp.stack(
        [jnp.where(fold, added, 0), jnp.where(fold, taken, 0)], axis=-1
    )
    pn = state.pn.at[
        frows, jnp.where(fold, slot, 0).astype(jnp.int32)
    ].add(pair, mode="drop")
    el = state.elapsed.at[frows].max(
        jnp.where(fold, jnp.maximum(elapsed, 0), 0), mode="drop"
    )
    return (
        LimiterState(pn=pn, elapsed=el), ok, live, live & hosted,
        slot, cap, added, taken, elapsed,
    )


class TestRawIngestObligations:
    """Device-resident ingest (ops/ingest.py decode_fold_raw): the full
    PTP001-005 set holds through real dv2 datagram bytes, and the seeded
    accept-bad-checksum / add-instead-of-max mutations are rejected."""

    def test_decode_fold_raw_proves_clean(self):
        assert prove.prove_root(ROOTS["decode_fold_raw"]) == []

    def test_full_obligations_declared(self):
        assert set(ROOTS["decode_fold_raw"].obligations) == set(prove.ALL_CODES)

    def test_accept_bad_checksum_rejected(self):
        f = prove.prove_root(
            ROOTS["decode_fold_raw"], fn=accept_bad_checksum_ingest
        )
        got = codes(f)
        # The corruption sweep: verdicts diverge from the python decoder
        # AND rejected planes leak values into state.
        assert "PTP003" in got

    def test_add_instead_of_max_fold_rejected(self):
        f = prove.prove_root(ROOTS["decode_fold_raw"], fn=add_fold_ingest)
        got = codes(f)
        # Structural taint (scatter-add on a merged plane), decoder
        # disagreement, and duplicated-plane idempotence all fire.
        assert "PTP001" in got and "PTP003" in got


def tail_dropping_tree_reduce(pn, elapsed):
    """Seeded flat-vs-tree divergence (pod-scale converge): a 'tree' that
    folds only the power-of-two replica prefix and silently drops the
    ragged tail — bit-identical to the real tree at R∈{2,4,8}, wrong at
    any ragged fan-in. The model's non-power-of-two sweep must catch it."""
    from patrol_tpu.parallel import topology as topo

    r = pn.shape[0]
    p = 1
    while p * 2 <= r:
        p *= 2
    return topo.tree_reduce_states(pn[:p], elapsed[:p])


def add_tree_reduce(pn, elapsed):
    """Interior tree nodes summing instead of max-joining: the classic
    reduce-tree refactor mistake (correct for a sum all-reduce, a
    disaster for a join)."""
    return LimiterState(pn=pn.sum(axis=0), elapsed=elapsed.sum(axis=0))


class TestTreeConvergeObligations:
    """The pod-scale mesh converge root (parallel.topology.
    tree_reduce_states): full obligation set, clean on the shipped
    butterfly schedule, and the seeded flat-vs-tree divergence + sum-tree
    mutations are demonstrably rejected."""

    def test_tree_converge_proves_clean(self):
        assert prove.prove_root(ROOTS["tree_reduce_states"]) == []

    def test_tree_converge_full_obligations_declared(self):
        assert set(ROOTS["tree_reduce_states"].obligations) == set(
            prove.ALL_CODES
        )

    def test_tail_dropping_tree_rejected(self):
        """The seeded flat-vs-tree divergence mutation: identical to the
        real schedule at every power-of-two fan-in, so only the model's
        ragged-R flat-equivalence check can reject it."""
        f = prove.prove_root(
            ROOTS["tree_reduce_states"], fn=tail_dropping_tree_reduce
        )
        got = codes(f)
        assert "PTP002" in got, got
        assert any("diverges from the flat join" in fi.message for fi in f)

    def test_sum_tree_rejected_by_both_passes(self):
        f = prove.prove_root(ROOTS["tree_reduce_states"], fn=add_tree_reduce)
        got = codes(f)
        # Structural taint (reduce_sum on a state plane) AND the model
        # (dup-leaf idempotence breaks; result diverges from flat max).
        assert "PTP001" in got and "PTP002" in got and "PTP003" in got

    def test_tree_matches_flat_on_stacked_states(self):
        """Direct spot check outside the model harness: random stacks at
        every fan-in class reduce to the elementwise max bit-exactly."""
        from patrol_tpu.parallel import topology as topo

        rng = np.random.default_rng(7)
        for r in (1, 2, 3, 4, 5, 8):
            pn = rng.integers(0, 1 << 50, (r, 6, 3, 2))
            el = rng.integers(0, 1 << 50, (r, 6))
            out = topo.tree_reduce_states(jnp.asarray(pn), jnp.asarray(el))
            assert np.array_equal(np.asarray(out.pn), pn.max(axis=0))
            assert np.array_equal(np.asarray(out.elapsed), el.max(axis=0))


# ---------------------------------------------------------------------------
# Bucket-lifecycle obligations (idle-bucket GC, ROADMAP item 4): the
# IsZero predicate's conservation suite, proven both ways.


def always_full_probe(state, probe, node_slot):
    """Seeded unsound predicate: declares every capacity-known bucket
    reclaimable, ignoring un-refilled spend — the exact
    'gc-drops-admitted-tokens' bug class."""
    from patrol_tpu.ops import lifecycle as lc

    out = lc.lifecycle_probe(state, probe, node_slot)
    return lc.LifecycleView(
        full=probe.cap_base_nt > 0,
        own_added_nt=out.own_added_nt,
        own_taken_nt=out.own_taken_nt,
        elapsed_ns=out.elapsed_ns,
    )


def flapping_probe(state, probe, node_slot):
    """Seeded non-monotone predicate: the verdict depends on clock parity,
    so a delayed sweep flips reclaim decisions."""
    from patrol_tpu.ops import lifecycle as lc

    out = lc.lifecycle_probe(state, probe, node_slot)
    return lc.LifecycleView(
        full=out.full & (probe.now_ns % 2 == 0),
        own_added_nt=out.own_added_nt,
        own_taken_nt=out.own_taken_nt,
        elapsed_ns=out.elapsed_ns,
    )


class TestLifecycleObligations:
    def test_shipped_predicate_proves_clean(self):
        assert prove.prove_root(ROOTS["lifecycle_probe"]) == []

    def test_unsound_predicate_rejected_as_token_loss(self):
        f = prove.prove_root(ROOTS["lifecycle_probe"], fn=always_full_probe)
        got = codes(f)
        assert "PTP002" in got, got
        assert any("loses admitted tokens" in fi.message for fi in f)

    def test_time_flapping_predicate_rejected(self):
        f = prove.prove_root(ROOTS["lifecycle_probe"], fn=flapping_probe)
        assert "PTP004" in codes(f)

    def test_kernel_matches_host_twin(self):
        """The numpy twin (host-resident lanes + soak digests) must agree
        with the kernel verdict bit-for-bit over a dense random grid."""
        from patrol_tpu.models.limiter import NANO, LimiterState
        from patrol_tpu.ops import lifecycle as lc

        rng = np.random.default_rng(12)
        B, N, K = 16, 3, 64
        pn = rng.integers(0, 4 * NANO, (B, N, 2)).astype(np.int64)
        pn[B // 2 :, [0, 2]] = 0  # rows holding only the own lane (slot 1)
        el = rng.integers(0, 3 * NANO, B).astype(np.int64)
        rows = rng.integers(0, B, K).astype(np.int64)
        now = rng.integers(0, 8 * NANO, K).astype(np.int64)
        per = rng.choice([0, NANO, 3600 * NANO], K).astype(np.int64)
        cap = rng.choice([0, NANO, 2 * NANO, 10 * NANO], K).astype(np.int64)
        created = rng.integers(0, 2 * NANO, K).astype(np.int64)
        st = LimiterState(pn=jnp.asarray(pn), elapsed=jnp.asarray(el))
        view = lc.lifecycle_probe_jit(
            st,
            lc.LifecycleProbe(
                rows=jnp.asarray(rows, jnp.int32),
                now_ns=jnp.asarray(now),
                per_ns=jnp.asarray(per),
                cap_base_nt=jnp.asarray(cap),
                created_ns=jnp.asarray(created),
            ),
            node_slot=1,
        )
        remote = (pn[rows][:, [0, 2]] != 0).any(axis=(1, 2))
        want = lc.host_lifecycle_full(
            pn[rows, :, 0].sum(axis=1), pn[rows, :, 1].sum(axis=1),
            el[rows], cap, created, now, per, remote,
        )
        assert want.any() and remote.any()  # both verdicts exercised
        assert np.array_equal(np.asarray(view.full), want)
        assert np.array_equal(np.asarray(view.own_added_nt), pn[rows, 1, 0])
        assert np.array_equal(np.asarray(view.own_taken_nt), pn[rows, 1, 1])
        assert np.array_equal(np.asarray(view.elapsed_ns), el[rows])
