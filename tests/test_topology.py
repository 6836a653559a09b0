"""Mesh scale-out tests on the 8-device virtual CPU mesh: sharded takes,
replica pmax-convergence, and exact equivalence with the single-device
kernels (the cross-device analogue of the CRDT law tests)."""

import random

import jax
import numpy as np
import pytest

from patrol_tpu.models.limiter import NANO, LimiterConfig, init_state
from patrol_tpu.ops.merge import merge_batch
from patrol_tpu.ops.take import take_batch
from patrol_tpu.parallel import topology as topo

CFG = LimiterConfig(buckets=64, nodes=4)
RATE_FREQ, RATE_PER = 10, NANO


pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device virtual CPU mesh"
)


def random_ops(rng, n_takes, n_deltas, now):
    rows = rng.sample(range(CFG.buckets), n_takes)  # unique per batch
    takes = [
        (
            row,
            now,
            RATE_FREQ,
            RATE_PER,
            rng.randrange(1, 4) * NANO,
            rng.randrange(1, 3),
            RATE_FREQ * NANO,
            0,
        )
        for row in rows
    ]
    deltas = [
        (
            rng.randrange(CFG.buckets),
            rng.randrange(CFG.nodes),
            rng.randrange(0, 5 * NANO),
            rng.randrange(0, 5 * NANO),
            rng.randrange(0, NANO),
        )
        for _ in range(n_deltas)
    ]
    return takes, deltas


def oracle_step(state, takes, deltas, node_slot):
    """Single-device reference: same merge-then-take ordering, global rows."""
    import jax.numpy as jnp
    from patrol_tpu.ops.merge import MergeBatch
    from patrol_tpu.ops.take import TakeRequest

    if deltas:
        mb = MergeBatch(
            rows=jnp.asarray([d[0] for d in deltas], jnp.int32),
            slots=jnp.asarray([d[1] for d in deltas], jnp.int32),
            added_nt=jnp.asarray([max(d[2], 0) for d in deltas], jnp.int64),
            taken_nt=jnp.asarray([max(d[3], 0) for d in deltas], jnp.int64),
            elapsed_ns=jnp.asarray([max(d[4], 0) for d in deltas], jnp.int64),
        )
        state = merge_batch(state, mb)
    results = {}
    if takes:
        req = TakeRequest(
            rows=jnp.asarray([t[0] for t in takes], jnp.int32),
            now_ns=jnp.asarray([t[1] for t in takes], jnp.int64),
            freq=jnp.asarray([t[2] for t in takes], jnp.int64),
            per_ns=jnp.asarray([t[3] for t in takes], jnp.int64),
            count_nt=jnp.asarray([t[4] for t in takes], jnp.int64),
            nreq=jnp.asarray([t[5] for t in takes], jnp.int64),
            cap_base_nt=jnp.asarray([t[6] for t in takes], jnp.int64),
            created_ns=jnp.asarray([t[7] for t in takes], jnp.int64),
        )
        state, res = take_batch(state, req, node_slot)
        for i, t in enumerate(takes):
            results[t[0]] = (int(res.have_nt[i]), int(res.admitted[i]))
    return state, results


class TestTreeConverge:
    """The hierarchical converge path (pod-scale serving): the butterfly
    tree reduce must be bit-exact against BOTH the flat all_gather join
    and the plain numpy max, on the real shard_map'd collective."""

    def _run_converge(self, mesh, replicas, pn_in, el_in, tree: bool):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from patrol_tpu.models.limiter import LimiterState

        def f(pn, el):
            st = topo.converge(
                LimiterState(pn=pn[0], elapsed=el[0]),
                replicas if tree else None,
            )
            return st.pn[None], st.elapsed[None]

        fn = jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(P(topo.REPLICA_AXIS), P(topo.REPLICA_AXIS)),
            out_specs=(P(topo.REPLICA_AXIS), P(topo.REPLICA_AXIS)),
            check_vma=False,
        )
        return jax.jit(fn)(jnp.asarray(pn_in), jnp.asarray(el_in))

    @pytest.mark.parametrize("replicas", [2, 4, 8])
    def test_tree_matches_flat_on_device(self, replicas):
        rng = np.random.default_rng(31 + replicas)
        mesh = topo.make_mesh(replicas=replicas)
        pn = rng.integers(0, 1 << 50, (replicas, 8, 4, 2))
        el = rng.integers(0, 1 << 50, (replicas, 8))
        tree_pn, tree_el = self._run_converge(mesh, replicas, pn, el, True)
        flat_pn, flat_el = self._run_converge(mesh, replicas, pn, el, False)
        want_pn = pn.max(axis=0)
        want_el = el.max(axis=0)
        for r in range(replicas):
            # Every replica holds the identical, exact global join —
            # tree and flat bit-for-bit.
            assert np.array_equal(np.asarray(tree_pn)[r], want_pn)
            assert np.array_equal(np.asarray(tree_el)[r], want_el)
            assert np.array_equal(np.asarray(flat_pn)[r], want_pn)
            assert np.array_equal(np.asarray(flat_el)[r], want_el)

    def test_non_power_of_two_falls_back_flat(self):
        """A ragged replica fan-in (3×2 mesh over 6 devices) routes
        through the flat all_gather fallback and still joins exactly."""
        rng = np.random.default_rng(99)
        mesh = topo.make_mesh(replicas=3, devices=jax.devices()[:6])
        pn = rng.integers(0, 1 << 50, (3, 4, 2, 2))
        el = rng.integers(0, 1 << 50, (3, 4))
        got_pn, got_el = self._run_converge(mesh, 3, pn, el, True)
        for r in range(3):
            assert np.array_equal(np.asarray(got_pn)[r], pn.max(axis=0))
            assert np.array_equal(np.asarray(got_el)[r], el.max(axis=0))

    def test_packed_step_matches_unpacked(self):
        """build_cluster_step_packed (the StagingPool transfer shape) is
        bit-exact against the unpacked step on identically routed work."""
        rng = random.Random(5)
        mesh = topo.make_mesh(replicas=2)
        plan = topo.plan_for(mesh, CFG)
        takes, deltas = random_ops(rng, n_takes=8, n_deltas=24, now=NANO)
        k = 16
        take_mat, merge_mat, placed = topo.route_packed(
            plan, takes, deltas, k, k
        )
        req, mb = topo.route_requests(plan, takes, deltas, k, k)

        s1 = topo.init_sharded_state(CFG, mesh)
        step = topo.build_cluster_step(mesh, 0)
        s1, res1 = step(s1, mb, req)

        s2 = topo.init_sharded_state(CFG, mesh)
        packed = topo.build_cluster_step_packed(mesh, 0)
        s2, out = packed(
            s2,
            jax.device_put(take_mat, topo.batch_sharding(mesh)),
            jax.device_put(merge_mat, topo.batch_sharding(mesh)),
        )
        assert (np.asarray(s1.pn) == np.asarray(s2.pn)).all()
        assert (np.asarray(s1.elapsed) == np.asarray(s2.elapsed)).all()
        out = np.asarray(out)
        assert np.array_equal(out[0], np.asarray(res1.have_nt))
        assert np.array_equal(out[1], np.asarray(res1.admitted))
        # placed indexes the packed result exactly like the routed one.
        for (blk, slot), t in zip(placed, takes):
            assert out[0][blk * k + slot] == int(
                np.asarray(res1.have_nt)[blk * k + slot]
            )


class TestMeshEquivalence:
    @pytest.mark.parametrize("replicas", [1, 2, 4, 8])
    def test_cluster_step_matches_single_device(self, replicas):
        rng = random.Random(11 + replicas)
        mesh = topo.make_mesh(replicas=replicas)
        plan = topo.plan_for(mesh, CFG)
        step = topo.build_cluster_step(mesh, node_slot=0)

        mesh_state = topo.init_sharded_state(CFG, mesh)
        oracle_state = init_state(CFG)

        for it in range(4):
            now = it * NANO
            takes, deltas = random_ops(rng, n_takes=12, n_deltas=24, now=now)
            req, mb = topo.route_requests(
                plan, takes, deltas, k_take=16, k_merge=16, deltas_to_home=True
            )
            mesh_state, res = step(mesh_state, mb, req)
            oracle_state, want = oracle_step(oracle_state, takes, deltas, 0)

            # Per-take results agree: find each take's slot in its block.
            have = np.asarray(res.have_nt)
            admitted = np.asarray(res.admitted)
            fill = [0] * plan.blocks
            for t in takes:
                row = t[0]
                replica, shard, _ = plan.locate(row)
                blk = plan.block_index(replica, shard)
                at = blk * 16 + fill[blk]
                fill[blk] += 1
                assert (int(have[at]), int(admitted[at])) == want[row], (
                    f"iter {it} row {row}"
                )

            # Full state is bit-identical after convergence.
            assert (np.asarray(mesh_state.pn) == np.asarray(oracle_state.pn)).all()
            assert (
                np.asarray(mesh_state.elapsed) == np.asarray(oracle_state.elapsed)
            ).all()

    def test_round_robin_deltas_converge_after_step(self):
        """Deltas ingested on arbitrary replicas still reach every replica
        via pmax: end-state equals home-routed ingestion."""
        rng = random.Random(99)
        mesh = topo.make_mesh(replicas=2)
        plan = topo.plan_for(mesh, CFG)
        step = topo.build_cluster_step(mesh, node_slot=0)

        _, deltas = random_ops(rng, 0, 32, 0)
        no_takes: list = []

        s1 = topo.init_sharded_state(CFG, mesh)
        req, mb = topo.route_requests(plan, no_takes, deltas, 8, 32, deltas_to_home=False)
        s1, _ = step(s1, mb, req)

        s2 = topo.init_sharded_state(CFG, mesh)
        req, mb = topo.route_requests(plan, no_takes, deltas, 8, 32, deltas_to_home=True)
        s2, _ = step(s2, mb, req)

        assert (np.asarray(s1.pn) == np.asarray(s2.pn)).all()
        assert (np.asarray(s1.elapsed) == np.asarray(s2.elapsed)).all()

    def test_block_overflow_raises(self):
        mesh = topo.make_mesh(replicas=2)
        plan = topo.plan_for(mesh, CFG)
        takes = [(0, 0, 10, NANO, NANO, 1, 10 * NANO, 0)] * 3
        with pytest.raises(ValueError, match="overflow"):
            topo.route_requests(plan, takes, [], k_take=2, k_merge=2)
