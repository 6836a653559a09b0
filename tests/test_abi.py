"""patrol-abi self-tests (PTA001-PTA005).

Every code is proven BOTH ways: the pass stays silent on the shipped
native library AND demonstrably rejects an injected defect — the seeded
fold mutation (perturb the Python-side reference fold, watch PTA001
refuse the now-divergent native output), a lying take model (PTA004's
differential is live, not vacuous), and an illegal unlock ordering
(PTA004's lock-protocol legality, judged from the declared effects
table). `TestRepoAbiClean` is the `pytest -m abi` slice of the
scripts/check.sh stage-5 contract.
"""

import dataclasses
import os

import numpy as np
import pytest

from patrol_tpu import native
from patrol_tpu.analysis import abi
from patrol_tpu.native import NATIVE_EFFECTS
from patrol_tpu.ops.obligations import ABI_OBLIGATIONS

pytestmark = [
    pytest.mark.abi,
    pytest.mark.skipif(
        native.load() is None, reason="native toolchain unavailable"
    ),
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NANO = abi.NANO

OBS = {ob.check: ob for ob in ABI_OBLIGATIONS}


@pytest.fixture(scope="module")
def lib():
    return abi._load_lib()


def codes(findings):
    return sorted({f.check for f in findings})


# --- PTA001: fold conformance ---------------------------------------------


class TestFoldConformance:
    def test_shipped_fold_is_silent(self, lib):
        assert abi.check_fold_conformance(OBS["fold_conformance"], lib) == []

    def test_seeded_mutation_of_reference_fold_is_rejected(
        self, lib, monkeypatch
    ):
        """THE gate's reason to exist: perturb the Python-side reference
        fold (the max→add class of refactor mistake, applied to the
        oracle so the shipped .so plays the role of the broken side) and
        the conformance pass must refuse the divergence."""
        orig = abi._reference_fold

        def add_fold(*args, **kw):
            out = orig(*args, **kw)
            if out is None:
                return None
            out = list(out)
            out[2] = out[2] + out[3]  # sparse added lane: join became add
            return tuple(out)

        monkeypatch.setattr(abi, "_reference_fold", add_fold)
        f = abi.check_fold_conformance(OBS["fold_conformance"], lib)
        assert "PTA001" in codes(f), f

    def test_kernel_root_mutation_is_rejected(self, lib, monkeypatch):
        """The twins resolve dynamically through PROVE_ROOTS: mutating the
        registered merge_batch (raw-path oracle) must break the
        state-level agreement too."""
        import jax.numpy as jnp

        import patrol_tpu.ops.merge as merge_mod
        from patrol_tpu.models.limiter import LimiterState

        def add_merge_batch(state, batch):
            pair = jnp.stack([batch.added_nt, batch.taken_nt], axis=-1)
            pn = state.pn.at[batch.rows, batch.slots].add(pair)
            elapsed = state.elapsed.at[batch.rows].max(batch.elapsed_ns)
            return LimiterState(pn=pn, elapsed=elapsed)

        monkeypatch.setattr(merge_mod, "merge_batch", add_merge_batch)
        f = abi.check_fold_conformance(OBS["fold_conformance"], lib)
        assert "PTA001" in codes(f)
        assert any("state diverges" in x.message for x in f)

    def test_native_fold_bails_exactly_like_reference(self, lib):
        # Bail parity is part of the contract: rc=-1 ⟺ reference None.
        bad_slot = np.array([[0, 9, 1, 0, 0]], np.int64)
        kw = dict(nodes=2, row_dense_min=2, max_distinct=8, cap_dense=8)
        assert abi._fold_of(lib, bad_slot, **kw) is None
        assert (
            abi._reference_fold(
                bad_slot[:, 0], bad_slot[:, 1], bad_slot[:, 2],
                bad_slot[:, 3], bad_slot[:, 4], **kw
            )
            is None
        )


# --- PTA001: classify conformance ------------------------------------------


class TestClassifyConformance:
    def test_shipped_classify_is_silent(self, lib):
        assert (
            abi.check_classify_conformance(OBS["classify_conformance"], lib)
            == []
        )

    def test_reference_mutation_is_rejected(self, lib, monkeypatch):
        """Same shape as the fold mutation: a perturbed reference
        classify (sanitize off by one nanotoken) must trip PTA001."""
        orig = abi._reference_classify

        def skewed(*args, **kw):
            rows, out_a, out_t, out_e, out_s = orig(*args, **kw)
            out_a = out_a + (rows >= 0)  # off-by-one on surviving entries
            return rows, out_a, out_t, out_e, out_s

        monkeypatch.setattr(abi, "_reference_classify", skewed)
        f = abi.check_classify_conformance(OBS["classify_conformance"], lib)
        assert "PTA001" in codes(f)

    def test_folded_duplicates_release_their_pin(self, lib):
        """The -4 dedup contract, driven raw: duplicates of one
        (row, slot, code) key leave exactly ONE pin on the row."""
        with abi._DirHarness(lib, [b"a"]) as d:
            b = abi._ClassifyBatch(
                names=[b"a"] * 3, lens=[1] * 3, slots=[0] * 3,
                added=[1.0, 5.0, 3.0], taken=[2.0, 0.0, 9.0],
                elapsed=[1, 2, 3], caps=[-1] * 3, lane_a=[-1] * 3,
                lane_t=[-1] * 3, no_trailer=[0] * 3,
            )
            rows, out_a, out_t, out_e, _ = abi._native_classify(
                lib, d, b, 2, now=5
            )
            assert rows.tolist() == [0, -4, -4]
            assert int(d.pins[0]) == 1
            # The survivor carries the elementwise max of the fold.
            assert (out_a[0], out_t[0], out_e[0]) == (5 * NANO, 9 * NANO, 3)


# --- PTA001: the rx structure walk -----------------------------------------


class TestRawWalkConformance:
    class _Shim:
        """Delegating facade over the real lib for seeded binding faults."""

        def __init__(self, lib, fn):
            self._lib = lib
            self.pt_raw_walk = fn

        def __getattr__(self, name):
            return getattr(self._lib, name)

    def test_shipped_walk_is_silent(self, lib):
        assert (
            abi.check_raw_walk_conformance(OBS["raw_walk_conformance"], lib)
            == []
        )

    def test_reference_mutation_is_rejected(self, lib, monkeypatch):
        """A reference walk that skips the checksum verdict disagrees with
        the native walk on the flipped rows: PTA001."""
        from patrol_tpu.ops import ingest

        orig = ingest.host_walk

        def lax(planes, lengths):
            w = orig(planes, lengths)
            return w._replace(ok=w.ok | (np.asarray(lengths) > 0))

        monkeypatch.setattr(ingest, "host_walk", lax)
        f = abi.check_raw_walk_conformance(OBS["raw_walk_conformance"], lib)
        assert codes(f) == ["PTA001"]
        assert any("ok diverges" in x.message for x in f)

    def test_lock_holding_binding_is_rejected(self, lib):
        import ctypes

        held = ctypes.PyDLL(lib._name).pt_raw_walk
        held.argtypes = lib.pt_raw_walk.argtypes
        held.restype = ctypes.c_int
        f = abi.check_raw_walk_conformance(
            OBS["raw_walk_conformance"], self._Shim(lib, held)
        )
        assert codes(f) == ["PTA001"]
        assert any("interpreter lock" in x.message for x in f)

    def test_argtype_drift_is_rejected(self, lib):
        import ctypes

        drifted = ctypes.CDLL(lib._name).pt_raw_walk
        types = list(lib.pt_raw_walk.argtypes)
        types[1] = types[6]  # lengths declared int32
        drifted.argtypes = types
        f = abi.check_raw_walk_conformance(
            OBS["raw_walk_conformance"], self._Shim(lib, drifted)
        )
        assert codes(f) == ["PTA001"]
        assert any("argument 1" in x.message for x in f)
        drifted.argtypes = types[:-1]
        f = abi.check_raw_walk_conformance(
            OBS["raw_walk_conformance"], self._Shim(lib, drifted)
        )
        assert any("C definition 19" in x.message for x in f)


# --- PTA001: the binding that keeps the interpreter lock -------------------


class TestHeldBindingConformance:
    class _Shim:
        """Delegating facade over the real lib, one symbol rebound."""

        def __init__(self, lib, name, fn):
            self._lib = lib
            setattr(self, name, fn)

        def __getattr__(self, name):
            return getattr(self._lib, name)

    HTTP = "patrol_tpu/native/patrol_http.cpp"

    def _check(self, lib):
        return abi.check_held_binding_conformance(
            OBS["held_binding_conformance"], lib
        )

    def _rebound(self, lib, dll, types=None):
        import ctypes

        fn = dll(lib._name).pt_http_answer_takes
        fn.argtypes = types or lib.pt_http_answer_takes.argtypes
        fn.restype = ctypes.c_int
        return self._Shim(lib, "pt_http_answer_takes", fn)

    def _edited(self, monkeypatch, old, new):
        """The native sources with one edit in the HTTP front's."""
        sources = abi._native_sources()
        assert sources[self.HTTP].count(old) == 1
        sources[self.HTTP] = sources[self.HTTP].replace(old, new)
        monkeypatch.setattr(abi, "_native_sources", lambda: sources)

    def test_shipped_binding_is_silent(self, lib):
        assert self._check(lib) == []

    def test_lock_releasing_hand_back_is_rejected(self, lib):
        import ctypes

        f = self._check(self._rebound(lib, ctypes.CDLL))
        assert codes(f) == ["PTA001"]
        assert any("drops the interpreter lock" in x.message for x in f)

    def test_undeclared_lock_holding_binding_is_rejected(self, lib):
        import ctypes

        held = ctypes.PyDLL(lib._name).pt_http_stats
        held.argtypes = lib.pt_http_stats.argtypes
        f = self._check(self._Shim(lib, "pt_http_stats", held))
        assert codes(f) == ["PTA001"]
        assert any(
            "pt_http_stats is bound through PyDLL" in x.message for x in f
        )

    def test_argtype_drift_is_rejected(self, lib):
        import ctypes

        types = list(lib.pt_http_answer_takes.argtypes)
        types[3] = types[4]  # statuses declared int64
        f = self._check(self._rebound(lib, ctypes.PyDLL, types))
        assert codes(f) == ["PTA001"]
        assert any("argument 3" in x.message for x in f)

    def test_hand_back_under_the_server_mutex_is_rejected(self, lib, monkeypatch):
        """The server's mu is held by the epoll thread while it waits on
        the host-lane store mutex: keeping the interpreter lock while
        waiting on it can deadlock the node."""
        self._edited(
            monkeypatch,
            "    std::lock_guard<std::mutex> ak(s->answer_mu, std::adopt_lock);\n"
            "    for (int i = 0; i < n; i++)\n"
            "      s->answers.push_back(",
            "    std::lock_guard<std::mutex> ak(s->mu);\n"
            "    for (int i = 0; i < n; i++)\n"
            "      s->answers.push_back(",
        )
        f = self._check(lib)
        assert codes(f) == ["PTA001"]
        assert any("takes mu, which" in x.message for x in f)

    def test_answer_lock_that_is_no_leaf_is_rejected(self, lib, monkeypatch):
        self._edited(
            monkeypatch,
            "    q.swap(s->answers);\n",
            "    q.swap(s->answers);\n"
            "    std::lock_guard<std::mutex> lk(s->mu);\n",
        )
        f = self._check(lib)
        assert codes(f) == ["PTA001"]
        assert any("takes answer_mu, which" in x.message for x in f)

    def test_native_call_into_python_is_rejected(self, lib, monkeypatch):
        self._edited(
            monkeypatch,
            "void serve_loop(Server* s) {",
            "static void cb() { PyGILState_Ensure(); }\n"
            "void serve_loop(Server* s) {",
        )
        f = self._check(lib)
        assert codes(f) == ["PTA001"]
        assert any("calls into Python" in x.message for x in f)

    def test_blocking_declaration_is_rejected(self, monkeypatch, lib):
        import patrol_tpu.native as native_mod

        table = dict(NATIVE_EFFECTS)
        table["pt_http_answer_takes"] = table["pt_http_answer_takes"]._replace(
            blocks=True
        )
        monkeypatch.setattr(native_mod, "NATIVE_EFFECTS", table)
        f = self._check(lib)
        assert codes(f) == ["PTA001"]
        assert any("declared to block" in x.message for x in f)


# --- PTA002/PTA003: merge laws on the native side ---------------------------


class TestNativeMergeLaws:
    def test_fold_order_and_duplication_freedom(self, lib):
        kw = dict(nodes=2, row_dense_min=2, max_distinct=8, cap_dense=8)
        batch = np.array(
            [[0, 0, 3, 1, 2], [1, 1, 1, 3, 0], [0, 0, 1, 2, 3], [1, 0, 2, 2, 1]],
            np.int64,
        )
        base = abi._fold_of(lib, batch, **kw)
        assert abi._fold_outputs_equal(
            base, abi._fold_of(lib, batch[::-1].copy(), **kw)
        )
        assert abi._fold_outputs_equal(
            base, abi._fold_of(lib, np.concatenate([batch, batch]), **kw)
        )

    def test_classify_agg_is_order_free(self, lib):
        with abi._DirHarness(lib, [b"a", b"b"]) as d:
            b = abi._ClassifyBatch(
                names=[b"a", b"b", b"a", b"b"], lens=[1] * 4,
                slots=[0, 1, 0, 1], added=[3.0, 1.0, 7.0, 2.0],
                taken=[1.0, 0.0, 0.5, 4.0], elapsed=[4, 3, 2, 1],
                caps=[-1] * 4, lane_a=[-1] * 4, lane_t=[-1] * 4,
                no_trailer=[0] * 4,
            )
            a1 = abi._classify_agg(abi._native_classify(lib, d, b, 2, 9), b)
            d.pins[:] = 0
            rev = b.subset([3, 2, 1, 0])
            a2 = abi._classify_agg(
                abi._native_classify(lib, d, rev, 2, 9), rev
            )
            assert a1 == a2


# --- PTA004: the schedule explorer ------------------------------------------


class TestScheduleExplorer:
    def test_builtin_scenarios_are_silent(self, lib):
        assert (
            abi.check_hls_interleavings(OBS["hls_interleavings"], lib) == []
        )

    def test_illegal_unlock_ordering_is_rejected(self, lib):
        """The ISSUE's injected defect: an unlock before the lock — the
        effects table (requires_host_mu on pt_hls_unlock) makes it a
        lock-protocol finding, not undefined behavior."""
        bad = abi.HlsScenario(
            name="bad-unlock",
            names=(b"k0",),
            cap_base=(2 * NANO,),
            scripts=(
                (abi.HlsOp("unlock"), abi.HlsOp("lock")),
                (abi.HlsOp("probe", name=b"k0", freq=3, per_ns=NANO),),
            ),
        )
        f = abi.explore_scenario(bad, lib)
        assert codes(f) == ["PTA004"]
        assert any("lock-protocol violation" in x.message for x in f)

    def test_locked_op_without_lock_is_rejected(self, lib):
        bad = abi.HlsScenario(
            name="bad-drain",
            names=(b"k0",),
            cap_base=(NANO,),
            scripts=((abi.HlsOp("drain"),),),
        )
        f = abi.explore_scenario(bad, lib)
        assert codes(f) == ["PTA004"]

    def test_leaked_lock_is_rejected(self, lib):
        bad = abi.HlsScenario(
            name="bad-leak",
            names=(b"k0",),
            cap_base=(NANO,),
            scripts=((abi.HlsOp("lock"), abi.HlsOp("drain")),),
        )
        f = abi.explore_scenario(bad, lib)
        assert any("leaked lock" in x.message for x in f)

    def test_self_deadlock_is_rejected(self, lib):
        bad = abi.HlsScenario(
            name="bad-reacquire",
            names=(b"k0",),
            cap_base=(NANO,),
            scripts=(
                (
                    abi.HlsOp("lock"),
                    abi.HlsOp("probe", name=b"k0", freq=1, per_ns=NANO),
                ),
            ),
        )
        f = abi.explore_scenario(bad, lib)
        assert any("self-deadlock" in x.message for x in f)

    def test_model_differential_is_live(self, lib, monkeypatch):
        """A lying model (off-by-one remaining) must produce findings in
        every scenario that probes — the differential is doing work."""
        orig = abi._HlsModel.probe

        def lying(self, op, now):
            rc, rem = orig(self, op, now)
            return rc, (rem + 1 if rc == 1 and rem is not None else rem)

        monkeypatch.setattr(abi._HlsModel, "probe", lying)
        f = abi.explore_scenario(abi.builtin_scenarios()[0], lib)
        assert codes(f) == ["PTA004"]
        assert any("diverges from the model" in x.message for x in f)

    def test_blocked_callers_defer_instead_of_interleaving(self, lib):
        """While a caller holds the store mutex, takes_host_mu ops of the
        others must not be scheduled — the lock/drain/unlock triple is
        atomic against probes in every enumerated schedule."""
        sc = abi.builtin_scenarios()[0]
        schedules, violations = abi._enumerate_schedules(
            sc, NATIVE_EFFECTS, 4096
        )
        assert violations == set()
        assert len(schedules) == 30  # 6 probe orders × 5 block positions
        for schedule in schedules:
            kinds = [op.kind for _, op in schedule]
            i = kinds.index("lock")
            assert kinds[i : i + 3] == ["lock", "drain", "unlock"]

    def test_token_conservation_post_invariant(self, lib):
        """The explicit native-bytes invariant: a 3-token bucket admits
        exactly 3 of 4 zero-refill-window takes in EVERY schedule."""
        f = abi.explore_scenario(abi.builtin_scenarios()[0], lib)
        assert f == []


# --- PTA005: effects-table completeness -------------------------------------


class TestRxRingSchedules:
    """PTA004 on the zero-copy rx ring (device-resident ingest): every
    lease/commit-vs-pump interleaving matches the lowest-free-first
    model on the shipped library, and seeded ownership bugs — a lease
    policy that hands out the wrong plane, a commit that accepts
    double-commits — are demonstrably rejected."""

    def test_shipped_ring_is_silent(self, lib):
        assert abi.check_rxring_interleavings(
            OBS["rxring_interleavings"], lib
        ) == []

    def test_registered_with_pta004(self):
        ob = OBS["rxring_interleavings"]
        assert ob.codes == ("PTA004",)
        assert ob.symbol == "pt_rx_ring_lease"

    class _Shim:
        """Delegating facade over the real lib for seeded mutations."""

        def __init__(self, lib):
            self._lib = lib

        def __getattr__(self, name):
            return getattr(self._lib, name)

    def test_seeded_wrong_lease_policy_rejected(self, lib):
        """A lease that returns the HIGHEST free plane instead of the
        lowest — plausible after a free-list refactor — diverges from
        the model and must fire PTA004."""
        shim = self._Shim(lib)

        def high_lease(h):
            a = lib.pt_rx_ring_lease(h)
            b = lib.pt_rx_ring_lease(h)
            if b < 0:
                return a
            lib.pt_rx_ring_commit(h, a)
            return b

        shim.pt_rx_ring_lease = high_lease
        f = abi.check_rxring_interleavings(OBS["rxring_interleavings"], shim)
        assert codes(f) == ["PTA004"]
        assert "lease" in f[0].message

    def test_seeded_double_commit_acceptance_rejected(self, lib):
        """A commit that silently accepts an un-leased plane (the
        use-after-recycle door) must fire PTA004 via the refusal probe."""
        shim = self._Shim(lib)

        def lax_commit(h, plane):
            rc = lib.pt_rx_ring_commit(h, plane)
            return 0 if rc == -22 else rc  # swallow EINVAL

        shim.pt_rx_ring_commit = lax_commit
        f = abi.check_rxring_interleavings(OBS["rxring_interleavings"], shim)
        assert codes(f) == ["PTA004"]

    def test_deferred_destroy_protects_leased_planes(self, lib):
        """destroy while a plane is leased must NOT free it: the handle
        refuses new leases, the outstanding commit still lands, and only
        then does the ring free (exercised via a fresh handle reusing
        the slot table without crashing)."""
        h = lib.pt_rx_ring_create(2, 4, 256)
        assert h >= 0
        plane = lib.pt_rx_ring_lease(h)
        assert plane >= 0
        ptr = lib.pt_rx_ring_plane(h, plane)
        assert ptr != 0
        assert lib.pt_rx_ring_destroy(h) == 0  # deferred
        assert lib.pt_rx_ring_lease(h) < 0  # closing: no new leases
        # The leased plane's memory is still live — write through the view.
        import ctypes

        buf = (ctypes.c_uint8 * 16).from_address(ptr)
        buf[0] = 0x5A
        assert lib.pt_rx_ring_commit(h, plane) == 0  # last commit frees


class TestEffectsTable:
    def test_table_is_complete_both_ways(self):
        assert abi.check_effects_table(OBS["effects_table"]) == []

    def test_missing_entry_is_rejected(self, monkeypatch):
        import patrol_tpu.native as native_mod

        trimmed = dict(NATIVE_EFFECTS)
        trimmed.pop("pt_http_poll")
        monkeypatch.setattr(native_mod, "NATIVE_EFFECTS", trimmed)
        f = abi.check_effects_table(OBS["effects_table"])
        assert codes(f) == ["PTA005"]
        assert any("pt_http_poll" in x.message for x in f)

    def test_stale_entry_is_rejected(self, monkeypatch):
        import patrol_tpu.native as native_mod

        bloated = dict(NATIVE_EFFECTS)
        bloated["pt_made_up"] = native_mod.NativeEffect(
            False, False, False, True
        )
        monkeypatch.setattr(native_mod, "NATIVE_EFFECTS", bloated)
        f = abi.check_effects_table(OBS["effects_table"])
        assert codes(f) == ["PTA005"]
        assert any("stale" in x.message for x in f)

    def test_locked_family_declares_the_protocol(self):
        """The explorer's legality rules lean on these exact bits."""
        for sym in (
            "pt_hls_host_locked", "pt_hls_unhost_locked",
            "pt_hls_drain_locked", "pt_hls_unlock",
        ):
            assert NATIVE_EFFECTS[sym].requires_host_mu, sym
        for sym in ("pt_hls_lock", "pt_hls_stats", "pt_hls_take_probe"):
            assert NATIVE_EFFECTS[sym].takes_host_mu, sym
        assert NATIVE_EFFECTS["pt_http_poll"].blocks
        assert not NATIVE_EFFECTS["pt_hls_events"].takes_host_mu

    @pytest.mark.parametrize(
        "symbol",
        [
            "pt_http_poll", "pt_http_complete_other", "pt_http_answer_takes",
            "pt_http_stats",
        ],
    )
    def test_http_front_argtypes_match_the_c_definition(self, symbol):
        """The ctypes registration passes exactly the C definition's
        parameters (pt_http_poll's req_ns parse-stamp array included): a
        missing pointer would shift every later argument."""
        import re

        lib = native.load()
        if lib is None:
            pytest.skip("native toolchain unavailable")
        src = os.path.join(
            os.path.dirname(native.__file__), "patrol_http.cpp"
        )
        with open(src) as f:
            text = f.read()
        m = re.search(rf"\nint {symbol}\(([^)]*)\)\s*{{", text)
        assert m, symbol
        params = [p for p in m.group(1).split(",") if p.strip()]
        assert len(getattr(lib, symbol).argtypes) == len(params)


# --- suppression + drivers ---------------------------------------------------


class TestSuppressionAndDrivers:
    def test_pta_codes_ride_the_lint_directive(self):
        from patrol_tpu.analysis.lint import Module

        mod = Module(
            "patrol_tpu/ops/x.py",
            "a = 1  # patrol-lint: disable=PTA001,PTA004\n",
        )
        assert mod.suppressed("PTA001", 1)
        assert mod.suppressed("PTA004", 1)
        assert not mod.suppressed("PTA002", 1)

    def test_abi_repo_filters_suppressed_findings(self, tmp_path, monkeypatch):
        from patrol_tpu.analysis.lint import Finding

        src = tmp_path / "patrol_tpu" / "ops"
        src.mkdir(parents=True)
        (src / "fake.py").write_text(
            "x = 1\ny = 2  # patrol-lint: disable=PTA001\n"
        )
        crafted = [
            Finding("PTA001", "patrol_tpu/ops/fake.py", 1, "kept"),
            Finding("PTA001", "patrol_tpu/ops/fake.py", 2, "suppressed"),
        ]
        monkeypatch.setattr(abi, "abi_all", lambda only=None: crafted)
        out = abi.abi_repo(str(tmp_path))
        assert [f.line for f in out] == [1]

    def test_cpp_findings_cannot_be_suppressed(self):
        """apply_suppressions must keep findings anchored in .cpp sources
        (no python directive table exists there to honor)."""
        from patrol_tpu.analysis.lint import Finding, apply_suppressions

        f = [Finding("PTA001", "patrol_tpu/native/patrol_host.cpp", 1, "x")]
        assert apply_suppressions(f, REPO_ROOT) == f


class TestRepoAbiClean:
    def test_repo_abi_proves_clean(self):
        """The stage-5 contract: zero findings, zero suppressions, on the
        shipped tree."""
        findings = abi.abi_repo(REPO_ROOT)
        assert findings == [], "\n".join(str(f) for f in findings)

    def test_registry_covers_the_native_joins(self):
        names = {ob.name for ob in ABI_OBLIGATIONS}
        for required in (
            "native.pt_fold_hybrid",
            "native.pt_rx_classify",
            "native.pt_raw_walk",
            "native.pt_http_answer_takes",
            "native.hls_schedules",
            "native.effects_table",
        ):
            assert required in names, required

    def test_every_code_is_declared_somewhere(self):
        declared = set()
        for ob in ABI_OBLIGATIONS:
            declared.update(ob.codes)
        assert declared == set(abi.ALL_CODES)

    def test_fold_twins_resolve_through_prove_roots(self):
        ob = OBS["fold_conformance"]
        twins = abi._resolve_twins(ob)
        assert set(twins) == set(ob.twins)
        for fn in twins.values():
            assert callable(fn)
