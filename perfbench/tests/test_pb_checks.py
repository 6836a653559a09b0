"""The reference's comparisons on small hand-made histories."""

import numpy as np
import pytest

from perfbench import checks

NANO = checks.NANO
FREQ, PER = 100, 10**9  # 100:1s, one token per 10 ms
CAP = FREQ * NANO


def test_refill_matches_the_float64_formula():
    assert checks.refill_grant_nt(10_000_000, FREQ, PER) == NANO  # 10 ms: one token
    assert checks.refill_grant_nt(0, FREQ, PER) == 0
    assert checks.refill_grant_nt(3, FREQ, PER) == 300
    # float64, not exact rationals: 1/3 of a 7-token interval.
    assert checks.refill_grant_nt(1, 3, 7 * 10**9) == int(1 / float(7 * 10**9 // 3) * 1e9)


def lane(*steps):
    """Published rows (key 0, added, taken, elapsed) from (dA, dT, dE) steps."""
    rows, a, t, e = [], 0, 0, 0
    for da, dt, de in steps:
        a, t, e = a + da, t + dt, e + de
        rows.append((0, a, t, e))
    return np.array(rows, np.int64)


SPEND0, SPEND1 = np.array([2 * NANO]), np.array([50 * NANO])


def faults(pub, tally=None):
    return checks.refill_faults(pub, FREQ, PER, SPEND0, SPEND1, tally)


def test_refill_steps_judged():
    tally = {}
    pub = lane(
        (3 * NANO, NANO, 5 * 10**9),  # first take: capped, added whole, spend 3 in view
        (1_234_567_800, NANO, 12_345_678),  # one take below the cap: the exact refill
        (2 * NANO - 7, 2 * NANO, 20_000_000),  # two takes: bounded by the refill of 20 ms
    )
    assert faults(pub, tally) == 0
    assert tally == {"exact": 1, "capped": 1, "bounded": 1}


@pytest.mark.parametrize("step", [
    (1_234_567_800 + 64, NANO, 12_345_678),  # refill off by a float32's rounding
    (1_234_567_800 - 64, NANO, 12_345_678),  # low, and not at the cap
    (3 * NANO, NANO, 20_000_000),  # more than the refill of the elapsed time
    (NANO, NANO // 2, 20_000_000),  # half a token taken
    (100 * NANO, NANO, 5 * 10**9),  # capped, but the spend in view was never shipped
])
def test_refill_faults_found(step):
    assert faults(lane((3 * NANO, NANO, 5 * 10**9), step)) == 1


def test_duplicates_and_order_do_not_matter():
    pub = lane((3 * NANO, NANO, 5 * 10**9), (1_234_567_800, NANO, 12_345_678))
    assert faults(np.concatenate([pub[::-1], pub])) == 0


def test_step_fn_is_the_running_max_per_key():
    f = checks.StepFn(np.array([1, 1, 2, 1]), np.array([10, 20, 15, 30]), np.array([5, 3, 9, 7]),
                      np.array([-1, -2, -3]))
    got = f.at(np.array([1, 1, 1, 1, 2, 2, 0]), np.array([9, 10, 25, 99, 14, 15, 50]))
    assert got.tolist() == [-2, 5, 5, 7, -3, 9, -1]


def answers(status, value, sent, done, own=((), (), ()), spend=((), (), ())):
    n = len(status)
    own_fn = checks.StepFn(*(np.array(x, np.int64) for x in own), np.zeros(1, np.int64)).at
    spend_fn = checks.StepFn(*(np.array(x, np.int64) for x in spend), np.zeros(1, np.int64)).at
    return checks.answer_faults(np.zeros(n, np.int64), np.array(sent), np.array(done),
                                np.array(status), np.array(value), FREQ, CAP, own_fn, spend_fn)


def test_answers_judged_against_the_least_balance():
    # Two 200s on a full bucket, then a 429 once 99 tokens were shipped as spent.
    assert answers([200, 200, 429], [99, 98, 0], [1, 2, 10], [3, 4, 11],
                   spend=([0], [5], [99 * NANO])) == 0
    # The same 429 before that spend was shipped: the node held 98 tokens.
    assert answers([200, 200, 429], [99, 98, 0], [1, 2, 10], [3, 4, 11],
                   spend=([0], [50], [99 * NANO])) == 1
    # A 200 that says 10 tokens are left where 97 were held for certain.
    assert answers([200, 200, 200], [99, 98, 10], [1, 2, 3], [3, 4, 5]) == 1
    # Refilled tokens the node published count to its balance.
    assert answers([429], [0], [10], [11], own=([0], [5], [2 * NANO]),
                   spend=([0], [1], [101 * NANO])) == 1
    # A wrong status, a 429 with a balance, a 200 at the capacity.
    assert answers([500, 429, 200], [0, 3, 100], [1, 2, 3], [2, 3, 4]) == 3
