"""patrol-race self-tests (PTR001-PTR005) — the `pytest -m race` slice
of the scripts/check.sh stage-7 gate.

Every code is proven BOTH ways: the clean form of each fixture (and the
real repo) passes, and a seeded violation of the same shape is flagged.
The dynamic half's four seeded epoll-seam mutations must each be
rejected by the exact code they target; the static half's fixtures cover
guarded-state, lock-graph, condvar-predicate, and buffer-ownership
violations. The last tests run the whole stage over the real tree —
including the regression that every ProfiledCondition consumer in
runtime/engine.py survives PTR005 non-vacuously.
"""

import ast
import os

import pytest

from patrol_tpu.analysis import race
from patrol_tpu.analysis.lint import Module

pytestmark = pytest.mark.race

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(findings):
    return sorted({f.check for f in findings})


# ===========================================================================
# Dynamic half — the epoll-seam schedule explorer.


class TestSeamClean:
    def test_every_builtin_scenario_proves_clean(self):
        for scenario in race.builtin_seam_scenarios():
            explored, findings = race.explore_seam(scenario, race.SEAM_CLEAN)
            assert findings == [], f"{scenario.name}: {findings}"
            # Non-vacuous: the DFS actually enumerated interleavings.
            assert explored > 10, f"{scenario.name} explored only {explored}"

    def test_deterministic_replay(self):
        sc = race.builtin_seam_scenarios()[1]
        sem, _ = race.SEAM_MUTATIONS["ring-slot-reuse-without-fence"]
        a = race.explore_seam(sc, sem)
        b = race.explore_seam(sc, sem)
        assert a[0] == b[0]
        assert [str(f) for f in a[1]] == [str(f) for f in b[1]]

    def test_check_seam_repo_is_clean(self):
        assert race.check_seam_repo() == []


class TestSeamMutations:
    @pytest.mark.parametrize("name", sorted(race.SEAM_MUTATIONS))
    def test_mutation_rejected_by_target_code(self, name):
        sem, expected_code = race.SEAM_MUTATIONS[name]
        findings = race.check_seam(sem)
        assert findings, f"mutation {name} produced no findings"
        assert expected_code in codes(findings), (
            f"{name} expected {expected_code}, got {codes(findings)}"
        )

    def test_lost_wakeup_witness_names_the_park(self):
        sem, _ = race.SEAM_MUTATIONS["completion-before-park"]
        findings = race.check_seam(sem)
        assert any("lost wakeup" in f.message for f in findings)
        # The witness schedule is printed so a CI failure replays by hand.
        assert any("schedule [" in f.message for f in findings)

    def test_slot_reuse_witness_names_the_recycled_slot(self):
        sem, _ = race.SEAM_MUTATIONS["ring-slot-reuse-without-fence"]
        findings = race.check_seam(sem)
        assert any("recycled" in f.message for f in findings)

    def test_unlocked_complete_crosses_generation_or_closed_conn(self):
        sem, _ = race.SEAM_MUTATIONS["ack-without-holding-mutex"]
        findings = race.check_seam(sem)
        assert any(
            "crossed a recycled ring slot" in f.message
            or "CLOSED conn" in f.message
            for f in findings
        )

    def test_lost_kick_witness_names_the_stalled_answers(self):
        sem, _ = race.SEAM_MUTATIONS["drain-before-eventfd-read"]
        findings = race.check_seam(sem)
        assert any("stalled answers" in f.message for f in findings)
        # The clean drain order strands nothing over the same scenarios.
        assert race.check_seam(race.SEAM_CLEAN) == []

    def test_unregistered_mutation_would_be_reported(self, monkeypatch):
        # A mutation the explorer cannot catch must surface as a finding
        # from check_seam_repo (the checker proves its own teeth).
        monkeypatch.setitem(
            race.SEAM_MUTATIONS, "no-op-mutation",
            (race.SEAM_CLEAN, "PTR002"),
        )
        findings = race.check_seam_repo()
        assert any(
            "no-op-mutation" in f.message and f.check == "PTR002"
            for f in findings
        )

    def test_findings_anchor_at_pt_http_poll(self):
        sem, _ = race.SEAM_MUTATIONS["completion-before-park"]
        f = race.check_seam(sem)[0]
        assert f.path == "patrol_tpu/native/patrol_http.cpp"
        assert f.line > 1  # resolved to the real definition line


# ===========================================================================
# Static half fixtures. Each fixture module is analyzed with an injected
# registry so the checks are exercised independent of the shipped one.

_FIX = "patrol_tpu/fixture.py"


def _static(src, guards=None, holders=None, aliases=None, retained=None,
            effects=None):
    return race.race_static(
        {_FIX: src},
        guards=guards if guards is not None else {},
        holders=holders if holders is not None else {},
        aliases=aliases if aliases is not None else {},
        retained=retained if retained is not None else {},
        effects=effects if effects is not None else {},
    )


_GUARD_FIXTURE_REGISTRY = {
    _FIX: {"Plane": {"_dirty": race.Guard("_mu", "rw")}}
}
_GUARD_MUTATE_REGISTRY = {
    _FIX: {"Plane": {"_dirty": race.Guard("_mu", "mutate")}}
}


class TestGuardedState:
    CLEAN = (
        "import threading\n"
        "class Plane:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self._dirty = {}\n"
        "    def offer(self, k, v):\n"
        "        with self._mu:\n"
        "            self._dirty[k] = v\n"
        "    def stats(self):\n"
        "        with self._mu:\n"
        "            return len(self._dirty)\n"
    )
    SEEDED = (
        "import threading\n"
        "class Plane:\n"
        "    def __init__(self):\n"
        "        self._mu = threading.Lock()\n"
        "        self._dirty = {}\n"
        "    def offer(self, k, v):\n"
        "        self._dirty[k] = v\n"
    )

    def test_clean_fixture_passes(self):
        assert _static(self.CLEAN, guards=_GUARD_FIXTURE_REGISTRY) == []

    def test_unlocked_mutation_flagged(self):
        f = _static(self.SEEDED, guards=_GUARD_FIXTURE_REGISTRY)
        assert codes(f) == ["PTR003"]
        assert "_dirty" in f[0].message and "_mu" in f[0].message

    def test_unlocked_read_flagged_in_rw_mode_only(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "    def peek(self):\n"
            "        return len(self._dirty)\n"
        )
        assert codes(_static(src, guards=_GUARD_FIXTURE_REGISTRY)) == ["PTR003"]
        assert _static(src, guards=_GUARD_MUTATE_REGISTRY) == []

    def test_mutating_method_call_counts_as_mutation(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "    def reset(self):\n"
            "        self._dirty.clear()\n"
        )
        assert codes(_static(src, guards=_GUARD_MUTATE_REGISTRY)) == ["PTR003"]

    def test_init_is_exempt(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "        self._dirty['seed'] = 1\n"
        )
        assert _static(src, guards=_GUARD_MUTATE_REGISTRY) == []

    def test_declared_holder_is_exempt(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "    def _flush_locked(self, k):\n"
            "        self._dirty.pop(k, None)\n"
        )
        assert codes(_static(src, guards=_GUARD_MUTATE_REGISTRY)) == ["PTR003"]
        assert _static(
            src,
            guards=_GUARD_MUTATE_REGISTRY,
            holders={_FIX: {"Plane._flush_locked": ("_mu",)}},
        ) == []

    def test_closure_does_not_inherit_definition_site_lock(self):
        # A callback defined under the lock RUNS later, without it.
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "    def sched(self, timer):\n"
            "        with self._mu:\n"
            "            def fire():\n"
            "                self._dirty.clear()\n"
            "            timer(fire)\n"
        )
        assert codes(_static(src, guards=_GUARD_MUTATE_REGISTRY)) == ["PTR003"]

    def test_condvar_alias_counts_as_the_lock(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._cond = threading.Condition(self._mu)\n"
            "        self._dirty = {}\n"
            "    def offer(self, k, v):\n"
            "        with self._cond:\n"
            "            self._dirty[k] = v\n"
        )
        assert codes(_static(src, guards=_GUARD_MUTATE_REGISTRY)) == ["PTR003"]
        assert _static(
            src,
            guards=_GUARD_MUTATE_REGISTRY,
            aliases={_FIX: {"Plane": {"_cond": "_mu"}}},
        ) == []

    def test_inline_suppression_wins(self):
        src = (
            "import threading\n"
            "class Plane:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._dirty = {}\n"
            "    def offer(self, k, v):\n"
            "        self._dirty[k] = v  "
            "# patrol-lint: disable=PTR003 (publish-once at startup)\n"
        )
        assert _static(src, guards=_GUARD_MUTATE_REGISTRY) == []


class TestLockGraph:
    def test_declared_order_nesting_is_clean(self):
        src = (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self._host_mu = threading.Lock()\n"
            "        self._state_mu = threading.Lock()\n"
            "    def absorb(self):\n"
            "        with self._host_mu:\n"
            "            with self._state_mu:\n"
            "                pass\n"
        )
        assert _static(src) == []

    def test_declared_order_inversion_flagged(self):
        src = (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self._host_mu = threading.Lock()\n"
            "        self._state_mu = threading.Lock()\n"
            "    def bad(self):\n"
            "        with self._state_mu:\n"
            "            with self._host_mu:\n"
            "                pass\n"
        )
        f = _static(src)
        assert codes(f) == ["PTR004"]
        assert "_evict_mu -> _host_mu -> _state_mu" in f[0].message

    def test_cycle_between_private_locks_flagged(self):
        src = (
            "import threading\n"
            "class W:\n"
            "    def __init__(self):\n"
            "        self._a_mu = threading.Lock()\n"
            "        self._b_mu = threading.Lock()\n"
            "    def fwd(self):\n"
            "        with self._a_mu:\n"
            "            with self._b_mu:\n"
            "                pass\n"
            "    def rev(self):\n"
            "        with self._b_mu:\n"
            "            with self._a_mu:\n"
            "                pass\n"
        )
        f = _static(src)
        assert codes(f) == ["PTR004"]
        assert "cycle" in f[0].message

    def test_two_classes_private_locks_never_alias(self):
        src = (
            "import threading\n"
            "class A:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q_mu = threading.Lock()\n"
            "    def fwd(self):\n"
            "        with self._mu:\n"
            "            with self._q_mu:\n"
            "                pass\n"
            "class B:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._q_mu = threading.Lock()\n"
            "    def rev(self):\n"
            "        with self._q_mu:\n"
            "            with self._mu:\n"
            "                pass\n"
        )
        # A._mu -> A._q_mu and B._q_mu -> B._mu are DIFFERENT lock pairs.
        assert _static(src) == []

    def test_native_takes_host_mu_call_closes_the_inversion(self):
        # pt_hls_stats is declared takes_host_mu in NATIVE_EFFECTS: calling
        # it under _state_mu IS the _state_mu -> _host_mu inversion.
        from patrol_tpu.analysis.lint import native_effects

        if not native_effects():  # pragma: no cover - numpy-less env
            pytest.skip("NATIVE_EFFECTS unavailable")
        src = (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self, lib):\n"
            "        self._state_mu = threading.Lock()\n"
            "        self.lib = lib\n"
            "    def bad_stats(self, out):\n"
            "        with self._state_mu:\n"
            "            self.lib.pt_hls_stats(0, out)\n"
        )
        f = _static(src)
        assert codes(f) == ["PTR004"]

    def test_holder_contract_seeds_graph_edges(self):
        src = (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self._host_mu = threading.Lock()\n"
            "        self._evict_mu = threading.Lock()\n"
            "    def _drop_locked(self):\n"
            "        with self._evict_mu:\n"
            "            pass\n"
        )
        # Declared to run under _host_mu, acquiring _evict_mu inverts.
        f = race.race_static(
            {_FIX: src},
            guards={}, aliases={}, retained={}, effects={},
            holders={_FIX: {"Eng._drop_locked": ("_host_mu",)}},
        )
        assert codes(f) == ["PTR004"]

    def test_inline_suppression_wins(self):
        src = (
            "import threading\n"
            "class Eng:\n"
            "    def __init__(self):\n"
            "        self._host_mu = threading.Lock()\n"
            "        self._state_mu = threading.Lock()\n"
            "    def bad(self):\n"
            "        with self._state_mu:\n"
            "            with self._host_mu:  "
            "# patrol-lint: disable=PTR004 (single-threaded shutdown)\n"
            "                pass\n"
        )
        assert _static(src) == []


class TestCondvarLoops:
    def test_predicate_loop_is_clean(self):
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "        self._jobs = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            while not self._jobs:\n"
            "                self._cond.wait()\n"
            "            return self._jobs.pop()\n"
        )
        assert _static(src) == []

    def test_if_guarded_wait_flagged(self):
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "        self._jobs = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            if not self._jobs:\n"
            "                self._cond.wait()\n"
            "            return self._jobs.pop()\n"
        )
        f = _static(src)
        assert codes(f) == ["PTR005"]
        assert "predicate loop" in f[0].message

    def test_wait_for_is_exempt(self):
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "        self._jobs = []\n"
            "    def get(self):\n"
            "        with self._cond:\n"
            "            self._cond.wait_for(lambda: self._jobs)\n"
            "            return self._jobs.pop()\n"
        )
        assert _static(src) == []

    def test_profiled_condition_ctor_is_detected(self):
        src = (
            "from patrol_tpu.utils import profiling\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._pcond = profiling.ProfiledCondition('q')\n"
            "    def park(self):\n"
            "        with self._pcond:\n"
            "            self._pcond.wait()\n"
        )
        assert codes(_static(src)) == ["PTR005"]

    def test_event_wait_is_not_a_condvar(self):
        src = (
            "import threading\n"
            "class T:\n"
            "    def __init__(self):\n"
            "        self._event = threading.Event()\n"
            "    def wait(self, timeout):\n"
            "        return self._event.wait(timeout)\n"
        )
        assert _static(src) == []

    def test_inline_suppression_wins(self):
        src = (
            "import threading\n"
            "class Q:\n"
            "    def __init__(self):\n"
            "        self._cond = threading.Condition()\n"
            "    def park_once(self):\n"
            "        with self._cond:\n"
            "            self._cond.wait()  "
            "# patrol-lint: disable=PTR005 (timeout-only park)\n"
        )
        assert _static(src) == []


class _FakeEffect:
    def __init__(self, owns_buffers=False, borrows_until="call"):
        self.owns_buffers = owns_buffers
        self.borrows_until = borrows_until


class TestOwnership:
    RETAINING_SRC = (
        "import numpy as np\n"
        "class Dir:\n"
        "    def __init__(self, lib, cap):\n"
        "        self.name_rows = np.zeros((cap, 256), np.uint8)\n"
        "        self.h = lib.pt_fix_create(cap, self.name_rows)\n"
    )

    def _effects(self):
        return {
            "pt_fix_create": _FakeEffect(True, "pt_fix_destroy"),
            "pt_fix_destroy": _FakeEffect(),
        }

    def _retained(self):
        return {_FIX: {"Dir": {"name_rows": "pt_fix_create"}}}

    def test_clean_fixture_passes(self):
        f = _static(
            self.RETAINING_SRC,
            retained=self._retained(), effects=self._effects(),
        )
        assert f == []

    def test_rebinding_retained_buffer_flagged(self):
        src = self.RETAINING_SRC + (
            "    def grow(self, cap):\n"
            "        self.name_rows = np.zeros((cap, 256), np.uint8)\n"
        )
        f = _static(src, retained=self._retained(), effects=self._effects())
        assert codes(f) == ["PTR003"]
        assert "use-after-recycle" in f[0].message

    def test_resizing_retained_buffer_flagged(self):
        src = self.RETAINING_SRC + (
            "    def grow(self, cap):\n"
            "        self.name_rows.resize((cap, 256))\n"
        )
        f = _static(src, retained=self._retained(), effects=self._effects())
        assert codes(f) == ["PTR003"]

    def test_undeclared_retained_callsite_flagged(self):
        src = (
            "import numpy as np\n"
            "class Dir:\n"
            "    def __init__(self, lib, cap):\n"
            "        self.other = np.zeros(cap, np.int64)\n"
            "        self.h = lib.pt_fix_create(cap, self.other)\n"
        )
        f = _static(src, retained=self._retained(), effects=self._effects())
        assert any(
            "not registered in RETAINED_BUFFERS" in x.message for x in f
        )

    def test_columns_must_be_self_consistent(self):
        effects = {
            "pt_fix_create": _FakeEffect(True, "call"),  # disagree
        }
        f = _static("x = 1\n", retained={}, effects=effects)
        assert any("columns disagree" in x.message for x in f)

    def test_completeness_both_ways(self):
        # owns_buffers symbol with no declared attrs → finding.
        f = _static(
            "x = 1\n",
            retained={},
            effects={"pt_fix_create": _FakeEffect(True, "pt_fix_create")},
        )
        assert any("RETAINED_BUFFERS" in x.message for x in f)
        # declared attrs whose symbol is not owns_buffers → finding.
        f = _static(
            "x = 1\n",
            retained=self._retained(),
            effects={"pt_fix_create": _FakeEffect(False, "call")},
        )
        assert any("must agree both ways" in x.message for x in f)

    def test_shipped_effects_table_declares_the_retainers(self):
        from patrol_tpu.native import NATIVE_EFFECTS

        owners = ("pt_dir_create", "pt_hls_create", "pt_rx_ring_create")
        for sym in owners:
            assert NATIVE_EFFECTS[sym].owns_buffers
            assert NATIVE_EFFECTS[sym].borrows_until in NATIVE_EFFECTS
        # Everything else borrows for the call only.
        for sym, eff in NATIVE_EFFECTS.items():
            if sym not in owners:
                assert not eff.owns_buffers, sym
                assert eff.borrows_until == "call", sym


# ===========================================================================
# The real repo proves clean — and the checks are non-vacuous on it.


class TestRepoClean:
    def test_stage7_is_clean_on_the_shipped_tree(self):
        assert race.race_repo(REPO_ROOT) == []

    def test_lock_graph_sees_the_engine_edges(self):
        # Non-vacuous: the shipped tree must yield the three known
        # declared-order edges (else the graph walk silently broke).
        srcs = race.race_sources(REPO_ROOT)
        mods = [Module(rp, s) for rp, s in sorted(srcs.items())]
        edges = {}
        takes = race._native_takes_host_mu()
        record = lambda s, d, rp, ln: edges.setdefault((s, d), (rp, ln))  # noqa: E731
        for m in mods:
            for cls, methods in race._class_methods(m.tree).items():
                for fn in methods.values():
                    race._walk_lock_edges(
                        fn, m, cls, race.LOCK_ALIASES, takes, record
                    )
        for edge in (
            ("_evict_mu", "_host_mu"),
            ("_evict_mu", "_state_mu"),
            ("_host_mu", "_state_mu"),
        ):
            assert edge in edges, f"missing observed edge {edge}"

    def test_guard_registry_matches_the_tree(self):
        # Every registered guard names a real attribute and a real lock
        # of a real class — a rename must fail here, not rot silently.
        srcs = race.race_sources(REPO_ROOT)
        for relpath, per_cls in race.GUARDS.items():
            tree = ast.parse(srcs[relpath])
            classes = {
                n.name: ast.dump(n)
                for n in ast.walk(tree)
                if isinstance(n, ast.ClassDef)
            }
            for cls, attrs in per_cls.items():
                assert cls in classes, f"{relpath}: no class {cls}"
                body = classes[cls]
                for attr, guard in attrs.items():
                    assert f"attr='{attr}'" in body, (
                        f"{relpath}::{cls} has no attribute {attr}"
                    )
                    assert f"attr='{guard.lock}'" in body, (
                        f"{relpath}::{cls} has no lock {guard.lock}"
                    )


class TestEngineCondvarRegression:
    """Every ProfiledCondition consumer in engine.py survives PTR005 —
    and the detector actually SEES them (non-vacuous both ways)."""

    def _engine_module(self):
        rel = "patrol_tpu/runtime/engine.py"
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
            return Module(rel, fh.read())

    def test_engine_condvars_are_detected(self):
        mod = self._engine_module()
        attrs = race._condvar_attrs(mod.tree)
        assert attrs.get("DeviceEngine") == {"_cond", "_pcond"}

    def test_engine_waits_all_sit_in_predicate_loops(self):
        mod = self._engine_module()
        assert race.check_condvar_loops(mod) == []
        # Non-vacuous: engine.py really parks on both condvars.
        waits = [
            node
            for node in ast.walk(mod.tree)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "wait"
            and isinstance(node.func.value, ast.Attribute)
            and node.func.value.attr in ("_cond", "_pcond")
        ]
        assert len(waits) >= 3, "engine.py lost its condvar parks?"

    def test_antientropy_worker_wait_survives(self):
        rel = "patrol_tpu/net/antientropy.py"
        with open(os.path.join(REPO_ROOT, rel), encoding="utf-8") as fh:
            mod = Module(rel, fh.read())
        assert race._condvar_attrs(mod.tree) == {"AntiEntropy": {"_cond"}}
        assert race.check_condvar_loops(mod) == []

    def test_seeded_engine_shaped_wait_is_flagged(self):
        # The same consumer shape with the loop removed must fire — the
        # regression above passes because the loops exist, not because
        # the check is blind to ProfiledCondition.
        src = (
            "from patrol_tpu.utils import profiling\n"
            "class DeviceEngine:\n"
            "    def __init__(self):\n"
            "        self._pcond = profiling.ProfiledCondition('c')\n"
            "        self._pending = []\n"
            "    def _complete_loop(self):\n"
            "        with self._pcond:\n"
            "            if not self._pending:\n"
            "                self._pcond.wait()\n"
        )
        f = _static(src)
        assert codes(f) == ["PTR005"]


class TestMeshGuardCoverage:
    """Pod-scale satellite: the mesh engine's new host-side shared state
    (tick-accounting metrics read by API threads while the feeder
    mutates them) is registered in GUARDS — stage 7 stays non-vacuous as
    the mesh path grows — and the guard demonstrably has teeth."""

    def test_mesh_engine_in_race_ensemble(self):
        assert "patrol_tpu/runtime/mesh_engine.py" in race.RACE_FILES
        g = race.GUARDS["patrol_tpu/runtime/mesh_engine.py"]["MeshEngine"]
        assert g["_mesh_metrics"].lock == "_mesh_mu"
        assert g["_mesh_metrics"].mode == "rw"

    def test_shipped_mesh_accesses_are_nonvacuous(self):
        # The shipped tree really touches the guarded attr from more than
        # one method (feeder accounting + stats reader) — a rename would
        # otherwise leave the guard checking nothing.
        src = race.race_sources(REPO_ROOT)["patrol_tpu/runtime/mesh_engine.py"]
        assert src.count("_mesh_metrics") >= 3
        assert src.count("_mesh_mu") >= 3

    def test_seeded_unlocked_mesh_metrics_mutation_flagged(self):
        src = (
            "import threading\n"
            "class MeshEngine:\n"
            "    def __init__(self):\n"
            "        self._mesh_mu = threading.Lock()\n"
            "        self._mesh_metrics = {}\n"
            "    def _apply_fused(self):\n"
            "        self._mesh_metrics['mesh_fused_dispatches'] = 1\n"
        )
        f = race.race_static(
            {"patrol_tpu/runtime/mesh_engine.py": src},
            guards=race.GUARDS,
            holders={},
            aliases={},
            retained={},
            effects={},
        )
        assert codes(f) == ["PTR003"]
        assert "_mesh_metrics" in f[0].message


class TestRxRingGuardCoverage:
    """Device-resident ingest satellite: the zero-copy rx ring's shared
    lease bookkeeping is registered in GUARDS (rx thread leases, engine
    completer commits), the retained plane views are pinned in
    RETAINED_BUFFERS against the owns_buffers row, and the discipline
    demonstrably has teeth (a seeded unlocked lease mutation → PTR003)."""

    def test_ring_state_registered(self):
        assert "patrol_tpu/native/__init__.py" in race.RACE_FILES
        g = race.GUARDS["patrol_tpu/native/__init__.py"]["RxRing"]
        assert g["_leased"].lock == "_mu" and g["_leased"].mode == "rw"
        r = race.RETAINED_BUFFERS["patrol_tpu/native/__init__.py"]["RxRing"]
        assert r["_views"] == "pt_rx_ring_create"

    def test_shipped_ring_accesses_are_nonvacuous(self):
        src = race.race_sources(REPO_ROOT)["patrol_tpu/native/__init__.py"]
        assert src.count("_leased") >= 3  # lease add, commit discard, init
        assert "pt_rx_ring_commit" in src

    def test_seeded_unlocked_lease_mutation_rejected(self):
        """A ring wrapper that mutates the lease set outside _mu — the
        exact slip a lease-path refactor could make (the commit callback
        runs on the completer thread) — must fire PTR003."""
        src = (
            "import threading\n"
            "class RxRing:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._leased = set()\n"
            "    def lease(self, idx):\n"
            "        self._leased.add(idx)\n"
        )
        guards = {
            _FIX: {"RxRing": {"_leased": race.Guard("_mu", "rw")}}
        }
        f = _static(src, guards=guards)
        assert codes(f) == ["PTR003"]
        assert "_leased" in f[0].message

    def test_locked_lease_mutation_clean(self):
        src = (
            "import threading\n"
            "class RxRing:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._leased = set()\n"
            "    def lease(self, idx):\n"
            "        with self._mu:\n"
            "            self._leased.add(idx)\n"
        )
        guards = {
            _FIX: {"RxRing": {"_leased": race.Guard("_mu", "rw")}}
        }
        assert _static(src, guards=guards) == []


class TestGcGuardCoverage:
    """Bucket-lifecycle satellite: the GC sweep's shared state (window
    anchor, reclaim/shed/compaction counters) is registered in GUARDS
    under _evict_mu — stage 7 covers the new reclaim paths — and the
    discipline demonstrably has teeth (a seeded unlocked mutation of a
    reclaim set is rejected as PTR003)."""

    GC_ATTRS = (
        "_gc_win_start", "_gc_reclaimed", "_gc_shed", "_gc_sweeps",
        "_gc_compactions",
    )

    def test_gc_state_registered_under_evict_mu(self):
        g = race.GUARDS["patrol_tpu/runtime/engine.py"]["DeviceEngine"]
        for attr in self.GC_ATTRS:
            assert g[attr].lock == "_evict_mu", attr
            assert g[attr].mode == "mutate", attr

    def test_shipped_gc_accesses_are_nonvacuous(self):
        # The shipped tree really mutates every declared GC attr (a
        # rename would leave the guard checking nothing).
        src = race.race_sources(REPO_ROOT)["patrol_tpu/runtime/engine.py"]
        for attr in self.GC_ATTRS:
            assert f"self.{attr}" in src, attr
        assert race.race_static(race.race_sources(REPO_ROOT)) == []

    def test_seeded_unlocked_reclaim_mutation_rejected(self):
        """An engine-shaped GC path that bumps the reclaim counter
        outside _evict_mu — the exact slip a future reclaim refactor
        could make — must fire PTR003."""
        src = (
            "import threading\n"
            "class DeviceEngine:\n"
            "    def __init__(self):\n"
            "        self._evict_mu = threading.Lock()\n"
            "        self._gc_reclaimed = 0\n"
            "    def gc_sweep(self, n):\n"
            "        self._gc_reclaimed += n\n"
        )
        guards = {
            _FIX: {
                "DeviceEngine": {
                    "_gc_reclaimed": race.Guard("_evict_mu", "mutate")
                }
            }
        }
        f = _static(src, guards=guards)
        assert codes(f) == ["PTR003"]
        assert "_gc_reclaimed" in f[0].message

    def test_locked_reclaim_mutation_clean(self):
        src = (
            "import threading\n"
            "class DeviceEngine:\n"
            "    def __init__(self):\n"
            "        self._evict_mu = threading.Lock()\n"
            "        self._gc_reclaimed = 0\n"
            "    def gc_sweep(self, n):\n"
            "        with self._evict_mu:\n"
            "            self._gc_reclaimed += n\n"
        )
        guards = {
            _FIX: {
                "DeviceEngine": {
                    "_gc_reclaimed": race.Guard("_evict_mu", "mutate")
                }
            }
        }
        assert _static(src, guards=guards) == []


class TestMembershipGuardCoverage:
    """Elastic-membership satellite: the SlotTable's runtime membership
    state (active-member map, monotone epoch, lane tombstones) is
    registered in GUARDS — admin calls and membership datagrams mutate
    it from different threads — and the discipline demonstrably has
    teeth (a seeded unlocked tombstone write → PTR003)."""

    MEMBER_ATTRS = ("_members", "_epoch", "_tombstones")

    def test_membership_state_registered(self):
        assert "patrol_tpu/net/replication.py" in race.RACE_FILES
        g = race.GUARDS["patrol_tpu/net/replication.py"]["SlotTable"]
        for attr in self.MEMBER_ATTRS:
            assert g[attr].lock == "_mu", attr
            assert g[attr].mode == "rw", attr
        # The resize quiesce flag rides the engine's work condvar in
        # BOTH files that touch it (feeder predicate + resize swap).
        eg = race.GUARDS["patrol_tpu/runtime/engine.py"]["DeviceEngine"]
        assert eg["_tick_paused"].lock == "_cond"
        mg = race.GUARDS["patrol_tpu/runtime/mesh_engine.py"]["MeshEngine"]
        assert mg["_tick_paused"].lock == "_cond"

    def test_shipped_membership_accesses_are_nonvacuous(self):
        # The shipped tree really touches every declared attr from more
        # than one method (join/leave/rejoin + the view reader) — a
        # rename would otherwise leave the guard checking nothing.
        src = race.race_sources(REPO_ROOT)["patrol_tpu/net/replication.py"]
        for attr in self.MEMBER_ATTRS:
            assert src.count(f"self.{attr}") >= 3, attr
        assert race.race_sources(REPO_ROOT)[
            "patrol_tpu/runtime/mesh_engine.py"
        ].count("_tick_paused") >= 2

    def test_seeded_unlocked_tombstone_mutation_flagged(self):
        """A table-shaped remove path that writes the tombstone map
        outside _mu — the exact slip a future membership refactor could
        make — must fire PTR003."""
        src = (
            "import threading\n"
            "class SlotTable:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._epoch = 0\n"
            "        self._tombstones = {}\n"
            "    def remove_member(self, slot):\n"
            "        with self._mu:\n"
            "            self._epoch += 1\n"
            "        self._tombstones[slot] = self._epoch\n"
        )
        f = race.race_static(
            {"patrol_tpu/net/replication.py": src},
            guards=race.GUARDS,
            holders={},
            aliases={},
            retained={},
            effects={},
        )
        assert codes(f) == ["PTR003"]
        assert "_tombstones" in f[0].message

    def test_locked_membership_mutation_clean(self):
        src = (
            "import threading\n"
            "class SlotTable:\n"
            "    def __init__(self):\n"
            "        self._mu = threading.Lock()\n"
            "        self._epoch = 0\n"
            "        self._tombstones = {}\n"
            "        self._members = {}\n"
            "    def remove_member(self, slot):\n"
            "        with self._mu:\n"
            "            self._epoch += 1\n"
            "            self._tombstones[slot] = self._epoch\n"
            "            self._members.pop(slot, None)\n"
        )
        f = race.race_static(
            {"patrol_tpu/net/replication.py": src},
            guards=race.GUARDS,
            holders={},
            aliases={},
            retained={},
            effects={},
        )
        assert f == []
