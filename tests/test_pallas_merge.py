"""Pallas scatter-merge kernel: interpret-mode equivalence with the XLA
scatter path (bit-exact), block planning, and padding safety."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from patrol_tpu.models.limiter import LimiterConfig, init_state
from patrol_tpu.ops import pallas_merge
from patrol_tpu.ops.merge import MergeBatch, merge_batch

R = pallas_merge.ROWS_PER_BLOCK


def xla_reference(cfg, rows, slots, added, taken, elapsed, base_state=None):
    state = base_state if base_state is not None else init_state(cfg)
    return merge_batch(
        state,
        MergeBatch(
            rows=jnp.asarray(rows, jnp.int32),
            slots=jnp.asarray(slots, jnp.int32),
            added_nt=jnp.asarray(added, jnp.int64),
            taken_nt=jnp.asarray(taken, jnp.int64),
            elapsed_ns=jnp.asarray(elapsed, jnp.int64),
        ),
    )


def rand_batch(rng, K, B, N, hi=10**15):
    return (
        np.array([rng.randrange(B) for _ in range(K)], np.int64),
        np.array([rng.randrange(N) for _ in range(K)], np.int64),
        np.array([rng.randrange(hi) for _ in range(K)], np.int64),
        np.array([rng.randrange(hi) for _ in range(K)], np.int64),
        np.array([rng.randrange(hi) for _ in range(K)], np.int64),
    )


class TestPrepare:
    def test_blocks_and_ranges(self):
        rows = np.array([0, 5, R, R + 1, 4 * R + 2], np.int64)
        order, block_ids, starts, ends, n_touched = pallas_merge.prepare(rows, 8 * R)
        assert n_touched == 3
        assert set(block_ids[:3].tolist()) == {0, 1, 4}
        # Padding ids are untouched blocks, all distinct.
        assert len(set(block_ids.tolist())) == len(block_ids)
        srt = rows[order]
        for g in range(len(block_ids)):
            seg = srt[starts[g] : ends[g]]
            assert ((seg // R) == block_ids[g]).all()
        # Every delta covered exactly once.
        assert sum(int(ends[g] - starts[g]) for g in range(len(block_ids))) == len(rows)

    def test_values_above_2_31_split_correctly(self):
        v = np.array([(3 << 32) + 7], np.int64)
        pair = v.view(np.int32).reshape(1, 2)
        assert pair[0, 0] == 7 and pair[0, 1] == 3


class TestInterpretEquivalence:
    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_random_batches_bit_exact(self, seed):
        rng = random.Random(seed)
        cfg = LimiterConfig(buckets=4 * R, nodes=8)
        K = 300
        rows, slots, added, taken, elapsed = rand_batch(rng, K, cfg.buckets, cfg.nodes)

        want = xla_reference(cfg, rows, slots, added, taken, elapsed)
        got = pallas_merge.merge_batch_pallas(
            init_state(cfg), rows, slots, added, taken, elapsed, interpret=True
        )
        assert (np.asarray(got.pn) == np.asarray(want.pn)).all()
        assert (np.asarray(got.elapsed) == np.asarray(want.elapsed)).all()

    def test_merge_into_nonzero_state(self):
        rng = random.Random(9)
        cfg = LimiterConfig(buckets=2 * R, nodes=4)
        pre_rows, pre_slots, a0, t0, e0 = rand_batch(rng, 100, cfg.buckets, cfg.nodes)
        base = xla_reference(cfg, pre_rows, pre_slots, a0, t0, e0)

        rows, slots, a, t, e = rand_batch(rng, 150, cfg.buckets, cfg.nodes)
        want = xla_reference(cfg, rows, slots, a, t, e, base_state=base)
        got = pallas_merge.merge_batch_pallas(
            base, rows, slots, a, t, e, interpret=True
        )
        assert (np.asarray(got.pn) == np.asarray(want.pn)).all()
        assert (np.asarray(got.elapsed) == np.asarray(want.elapsed)).all()

    def test_duplicates_same_row_slot(self):
        cfg = LimiterConfig(buckets=R, nodes=4)
        rows = np.array([5, 5, 5], np.int64)
        slots = np.array([2, 2, 2], np.int64)
        a = np.array([9, 3, 7], np.int64)
        t = np.array([1, 8, 2], np.int64)
        e = np.array([4, 4, 6], np.int64)
        got = pallas_merge.merge_batch_pallas(
            init_state(cfg), rows, slots, a, t, e, interpret=True
        )
        assert int(got.pn[5, 2, 0]) == 9
        assert int(got.pn[5, 2, 1]) == 8
        assert int(got.elapsed[5]) == 6

    def test_values_beyond_2_32(self):
        """Exercise the lexicographic pair-max across the 32-bit boundary."""
        cfg = LimiterConfig(buckets=R, nodes=2)
        rows = np.array([1, 1], np.int64)
        slots = np.array([0, 0], np.int64)
        big, small = (5 << 32) + 1, (4 << 32) + 0xFFFFFFFF
        a = np.array([small, big], np.int64)
        t = np.array([big, small], np.int64)
        e = np.array([2**40 + 3, 2**40 + 2], np.int64)
        got = pallas_merge.merge_batch_pallas(
            init_state(cfg), rows, slots, a, t, e, interpret=True
        )
        assert int(got.pn[1, 0, 0]) == big
        assert int(got.pn[1, 0, 1]) == big
        assert int(got.elapsed[1]) == 2**40 + 3

    def test_single_block_single_delta(self):
        cfg = LimiterConfig(buckets=R, nodes=2)
        got = pallas_merge.merge_batch_pallas(
            init_state(cfg),
            np.array([0], np.int64),
            np.array([1], np.int64),
            np.array([42], np.int64),
            np.array([0], np.int64),
            np.array([0], np.int64),
            interpret=True,
        )
        assert int(got.pn[0, 1, 0]) == 42
