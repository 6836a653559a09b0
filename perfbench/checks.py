"""The plain reference and the comparisons that decide ``correct``.

Nothing here imports the program. The reference is the token bucket of
the rate limiter's contract, written out: the refill of ``freq`` tokens
per period, in IEEE float64 and floored to nanotokens, capped at the
capacity; a take of one token admitted while a whole token is left; and
the bucket's balance as the join of its lanes (capacity base plus every
lane's ``added`` less every lane's ``taken``), a lane's value being the
largest its owner ever shipped. The node's view of its peers' lanes at
any instant lies between nothing of the window and all they had shipped
by then; its own lane is what it published. The numbers compared:

``answer_faults``
    takes whose answer breaks the contract for ``count=1``: a status
    other than 200 or 429, no answer, a 200 whose balance left is not
    below the capacity, a 429 whose balance is not 0, and any answer
    that the node's view cannot give: a 429 while the node held a whole
    token for certain, or a 200 that leaves less than the node held for
    certain. "For certain" is the least the balance can have been
    between the take's send and its answer: the node's own ``added`` as
    published before the send, less one token for every 200 of that key
    sent before the answer, less all the spend the peers had shipped by
    then. A refill only adds to it.
``refill_faults``
    steps of the node's own lane, as it published it, that the
    reference's refill cannot give. Each publish carries the lane's
    ``added`` and ``taken`` and the bucket's elapsed clock; between two
    publishes of a key ``taken`` grows by one token per admitted take,
    and ``added`` by at most the refill of the elapsed time (one
    nanotoken of flooring per take). Where one take lies between them,
    ``added`` grew by exactly the reference's refill of the elapsed
    time, or by less because the bucket came to its capacity, which
    puts ``added`` on a whole token with the peers' spend in view
    between its first and its last value.
``own_lane_gap_keys``
    keys whose ``taken`` lane, as the node last published it, differs
    from one token per 200 the clients received: every admitted take is
    booked once, and nothing else is.
``readback_gap_keys``
    sampled keys whose ``GET /tokens`` answer differs from the join of
    the lanes the peers shipped (each acknowledged by the node) and the
    node's own lane, whose steps ``refill_faults`` checks.
``rx_errors``
    datagrams the node or the peers could not decode.

Each limit is 0: every comparison is exact.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

NANO = 1_000_000_000
GRANT_CLIP = float(2**62)

LIMITS = {
    "answer_faults": 0,
    "refill_faults": 0,
    "own_lane_gap_keys": 0,
    "readback_gap_keys": 0,
    "rx_errors": 0,
}


def refill_grant_nt(delta_ns: int, freq: int, per_ns: int) -> int:
    """The refill of ``delta_ns`` at ``freq`` tokens per ``per_ns``, in
    nanotokens before the capacity cap: ``float64(delta) /
    float64(per // freq) * 1e9``, floored, as the reference computes it."""
    interval = per_ns // freq if freq else 0
    if interval == 0 or delta_ns <= 0:
        return 0
    return int(math.floor(min(max(float(delta_ns) / float(interval) * float(NANO), 0.0), GRANT_CLIP)))


class StepFn:
    """Per key, the largest value of any event at or before a time (a
    monotone lane as seen at that time); ``base`` where there is none."""

    # Times (ns from the earliest, under 19 hours) and values (nanotokens)
    # both stay under 2**46, and keys under 2**17.
    _SPAN = 1 << 46

    def __init__(self, keys: np.ndarray, times: np.ndarray, values: np.ndarray, base: np.ndarray):
        self.base = base
        self.t0 = int(times.min()) if len(times) else 0
        order = np.lexsort((times, keys))
        self.keys = keys[order].astype(np.int64)
        self.comp = self.keys * self._SPAN + (times[order] - self.t0)
        v = values[order].astype(np.int64)
        if len(v) and not (0 <= v.min() and v.max() < self._SPAN):
            raise ValueError("lane values out of range")
        # A running maximum that restarts at each key: the key in the
        # high bits keeps one key's values from reaching the next's.
        hi = self.keys * self._SPAN
        self.vals = np.maximum.accumulate(hi + v) - hi if len(v) else v

    def at(self, keys: np.ndarray, times: np.ndarray) -> np.ndarray:
        keys = keys.astype(np.int64)
        t = np.clip(times - self.t0, -1, self._SPAN - 1)
        idx = np.searchsorted(self.comp, keys * self._SPAN + t, side="right") - 1
        ok = idx >= 0
        safe = np.where(ok, idx, 0)
        ok &= self.keys[safe] == keys if len(self.keys) else np.zeros(len(keys), bool)
        return np.where(ok, self.vals[safe] if len(self.vals) else 0, self.base[keys])


def count_before(keys: np.ndarray, times: np.ndarray, qkeys: np.ndarray, qtimes: np.ndarray) -> np.ndarray:
    """For each query, the events of the same key at or before its time."""
    span = StepFn._SPAN
    t0 = min(int(times.min()) if len(times) else 0, int(qtimes.min()) if len(qtimes) else 0)
    comp = np.sort(keys.astype(np.int64) * span + (times - t0))
    q = qkeys.astype(np.int64) * span
    return (np.searchsorted(comp, q + np.clip(qtimes - t0, 0, span - 1), side="right")
            - np.searchsorted(comp, q, side="left"))


def peer_spend_at(shipped: Sequence[List[Tuple[int, np.ndarray, np.ndarray]]], n_keys: int):
    """→ f(keys, times): the peers' ``taken`` summed over peers, as
    shipped at or before each time (the most a node can have held)."""
    fns = []
    for log in shipped:
        if log:
            t = np.concatenate([np.full(len(k), at, np.int64) for at, k, _ in log])
            k = np.concatenate([k for _, k, _ in log])
            v = np.concatenate([v for _, _, v in log])
        else:
            t = k = v = np.zeros(0, np.int64)
        fns.append(StepFn(k, t, v, np.zeros(n_keys, np.int64)))
    return lambda keys, times: sum(f.at(keys, times) for f in fns)


def answer_faults(
    keys: np.ndarray,
    sent: np.ndarray,
    done: np.ndarray,
    status: np.ndarray,
    value: np.ndarray,
    freq: int,
    cap_nt: int,
    own_added_at,
    peer_spend,
) -> int:
    """Takes (``count=1``) whose answer breaks the contract or cannot
    come from the node's view (module docstring). ``sent``/``done`` are
    on the clock of ``own_added_at``'s and ``peer_spend``'s times."""
    ok = status == 200
    denied = status == 429
    bad = ~(ok | denied)
    bad |= ok & ((value < 0) | (value >= freq))
    bad |= denied & (value != 0)
    judged = ok | denied
    k, s, d = keys[judged], sent[judged], done[judged]
    ok200 = status == 200
    n200 = count_before(keys[ok200], sent[ok200], k, d) - ok[judged]
    least = cap_nt + own_added_at(k, s) - n200 * NANO - peer_spend(k, d)
    held = least // NANO  # whole tokens the node held for certain
    wrong = np.zeros(len(k), bool)
    wrong |= denied[judged] & (held >= 1)
    wrong |= ok[judged] & (value[judged] < held - 1)
    bad[np.flatnonzero(judged)[wrong]] = True
    return int(bad.sum())


def refill_faults(
    published: np.ndarray,
    freq: int,
    per_ns: int,
    spend_first: np.ndarray,
    spend_last: np.ndarray,
    tally: Optional[Dict[str, int]] = None,
) -> int:
    """Steps of the node's published own lane that the reference refill
    cannot give (module docstring). ``published`` rows are (key, added,
    taken, elapsed); ``spend_first``/``spend_last`` are the peers' summed
    ``taken`` of each key before the window and at its end. ``tally``,
    where given, counts the steps by how they were judged: ``exact``
    (one take, refill below the cap), ``capped`` (one take, the bucket
    came to its capacity) and ``bounded`` (several takes)."""
    tally = {} if tally is None else tally
    for k in ("exact", "capped", "bounded"):
        tally[k] = 0
    if len(published) == 0:
        return 0
    rows = np.unique(published, axis=0)
    rows = rows[np.lexsort((rows[:, 3], rows[:, 1], rows[:, 2], rows[:, 0]))]
    interval = per_ns // freq
    faults = 0
    prev_key, pa, pt, pe = -1, 0, 0, 0
    for key, a, t, e in rows.tolist():
        if key != prev_key:
            prev_key, pa, pt, pe = key, 0, 0, 0  # the lane before any take
        da, dt, de = a - pa, t - pt, e - pe
        s = dt // NANO
        if dt <= 0 or dt % NANO or da < 0 or de < 0:
            faults += 1
        elif da > de * NANO // interval + s:
            faults += 1
        elif s == 1:
            grant = refill_grant_nt(de, freq, per_ns)
            capped = a % NANO == 0 and spend_first[key] <= a - pt <= spend_last[key]
            if da > grant or (da < grant and not capped):
                faults += 1
            tally["exact" if da == grant else "capped"] += 1
        else:
            tally["bounded"] += 1
        pa, pt, pe = a, t, e
    return faults


def admitted_per_key(keys: np.ndarray, status: np.ndarray, n_keys: int) -> np.ndarray:
    return np.bincount(keys[status == 200], minlength=n_keys).astype(np.int64)


def own_lane_gap_keys(admitted: np.ndarray, node_taken: np.ndarray) -> int:
    return int((node_taken != admitted * NANO).sum())


def reference_tokens(
    cap_nt: int,
    peer_added: List[np.ndarray],
    peer_taken: List[np.ndarray],
    node_added: np.ndarray,
    admitted: np.ndarray,
    sample: np.ndarray,
) -> np.ndarray:
    """The join's whole-token balance of each sampled key, floored at 0."""
    added = node_added[sample] + sum(a[sample] for a in peer_added)
    taken = admitted[sample] * NANO + sum(t[sample] for t in peer_taken)
    return np.maximum(cap_nt + added - taken, 0) // NANO


def readback_gap_keys(got: Dict[int, Optional[int]], want: np.ndarray, sample: np.ndarray) -> int:
    return int(sum(1 for i, k in enumerate(sample.tolist()) if got.get(k) != int(want[i])))


def correct(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in numbers)
