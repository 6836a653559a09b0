"""The batched take kernel — the reference's hot inner computation
(``Bucket.Take``, bucket.go:186-225) re-expressed as one branch-free JAX
kernel over a microbatch of requests.

Where the reference serializes takes under a per-bucket mutex
(bucket.go:21,187), this kernel admits a whole microbatch in one device
call. Contention on a hot bucket is handled *algebraically* instead of with
locks: the host batcher coalesces same-(bucket, rate, count) requests into a
single kernel row carrying ``nreq`` (how many identical requests queued) and
the kernel computes how many of them fit greedily — exactly the result of
running the reference's sequential takes at the same timestamp, where only
the first take refills (delta becomes 0 for the rest).

Fixed-point arithmetic notes: state is int64 nanotokens; the refill grant is
the reference's float64 expression (``float64(d) / float64(interval)``,
bucket.go:130-143, times 1e9, floored) evaluated by :func:`refill_grant_nt`
in int64 operations that round exactly as IEEE-754 does. The TPU has no
float64 unit: XLA's emulation differed from the host in 995 of 4096 seeded
grid rows on a v5e (chip_smoke.py's refill phase), and buckets move between
the host tiers (``HostLanes``, the C++ store) and device rows, so the two
must agree bit for bit on every backend.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from patrol_tpu.models.limiter import ADDED, TAKEN, NANO, LimiterState

# Refill grants are clipped here before the float64→int64 cast to keep the
# cast defined; any realistic grant is far below (and the capacity cap is
# applied after, in exact int64).
_GRANT_CLIP = float(2**62)
_MANT = 53  # float64 significand bits, the implicit one included


def host_refill_grant_nt(delta: int, freq: int, per_ns: int) -> int:
    """The host twin of :func:`refill_grant_nt` (and of the C++ store's
    ``hls_take_locked``): the reference's float64 refill in nanotokens,
    before the capacity clamp. ``interval`` is the truncating per/freq."""
    interval = per_ns // freq if freq else 0
    if freq == 0 or per_ns == 0 or interval == 0:
        return 0
    grant_f = (float(delta) / float(interval)) * float(NANO)
    return int(math.floor(min(max(grant_f, 0.0), _GRANT_CLIP)))


def _bitlen(x: jax.Array) -> jax.Array:
    """Significant bits of int64 ``x >= 0`` (0 for 0), by binary search."""
    n = jnp.zeros_like(x)
    for sh in (32, 16, 8, 4, 2, 1):
        t = x >> sh
        nz = t != 0
        x = jnp.where(nz, t, x)
        n = n + jnp.where(nz, sh, 0)
    return n + (x != 0)


def _round_even(m, low, half, sticky, e):
    """Round significand ``m`` (discarded bits ``low`` against ``half``,
    plus a sticky bit) to nearest, ties to even; renormalize a carry out
    of 53 bits. → (m in [2^52, 2^53), e)."""
    up = (low > half) | ((low == half) & (sticky | ((m & 1) == 1)))
    m = m + up
    carry = m == (1 << _MANT)
    return jnp.where(carry, 1 << (_MANT - 1), m), e + carry


def _to_double(x: jax.Array):
    """int64 ``x > 0`` as the nearest float64 ``m · 2^e``, m normalized."""
    n = _bitlen(x)
    up = jnp.maximum(_MANT - n, 0)
    dn = jnp.maximum(n - _MANT, 0)  # 0..10 bits fall off above 2^53
    m = (x << up) >> dn
    low = x & ((jnp.int64(1) << dn) - 1)
    half = jnp.where(dn > 0, jnp.int64(1) << jnp.maximum(dn - 1, 0), 1)
    return _round_even(m, low, half, False, n - _MANT)


@jax.jit  # one executable for eager callers; inlined under an outer jit
def refill_grant_nt(delta: jax.Array, interval: jax.Array) -> jax.Array:
    """``floor(clip((float64(delta) / float64(interval)) · 1e9, 0, 2^62))``
    for int64 ``delta >= 0`` and ``interval >= 1``, bit-identical to IEEE
    float64 (:func:`host_refill_grant_nt`) but in int64 operations only:
    each of the three float operations is carried out on integer
    significands and rounded to nearest-even, as IEEE prescribes."""
    i64 = jnp.int64
    ma, ea = _to_double(jnp.maximum(delta, 1))
    mb, eb = _to_double(interval)
    # q = floor(ma · 2^55 / mb) ∈ [2^54, 2^56) by restoring division; the
    # remainder is the sticky bit of the quotient.
    d0 = ma >= mb
    rem = ma - jnp.where(d0, mb, 0)

    def step(_, carry):
        q, rem = carry
        rem = rem << 1
        bit = rem >= mb
        return (q << 1) | bit.astype(i64), rem - jnp.where(bit, mb, 0)

    q, rem = jax.lax.fori_loop(0, 55, step, (d0.astype(i64), rem))
    s = jnp.where(q >= (i64(1) << 55), 3, 2)
    mq, eq = _round_even(
        q >> s, q & ((i64(1) << s) - 1), i64(1) << (s - 1), rem != 0,
        ea - eb - 55 + s,
    )
    # P = mq · 1e9 < 2^83 as A·2^32 + B, then rounded back to 53 bits.
    a = (mq >> 32) * NANO
    b = (mq & 0xFFFFFFFF) * NANO
    s = jnp.where((a << 3) + (b >> 29) >= (i64(1) << _MANT), 30, 29)
    mp, e = _round_even(
        (a << (32 - s)) + (b >> s), b & ((i64(1) << s) - 1), i64(1) << (s - 1),
        False, eq + s,
    )
    # floor(mp · 2^e), clipped at 2^62 (reached exactly when e >= 10).
    whole = jnp.where(
        e >= 0, mp << jnp.clip(e, 0, 9), mp >> jnp.clip(-e, 0, 63)
    )
    grant = jnp.where(e >= 10, i64(2**62), whole)
    return jnp.where(delta > 0, grant, 0)

# Packed-transfer layout of one take tick (engine._apply_takes ↔
# engine._jit_take_packed): the host ships ONE int64[TAKE_PACK_ROWS, K]
# request matrix (rows, now_ns, freq, per_ns, count_nt, nreq,
# cap_base_nt, created_ns) and receives ONE int64[TAKE_RESULT_ROWS, K]
# result matrix (have, admitted, own_added, own_taken, elapsed,
# sum_added, sum_taken). Fixed shapes per padded K, so the engine's
# StagingPool recycles the exact request buffers across ticks.
TAKE_PACK_ROWS = 8
TAKE_RESULT_ROWS = 7


class TakeRequest(NamedTuple):
    """A microbatch of K take requests. All arrays have leading dim K.

    Invariants maintained by the host batcher:
      * ``rows`` are unique among rows with ``nreq > 0`` (duplicates are
        coalesced into ``nreq``); padding rows have ``nreq == 0`` and commit
        nothing.
      * ``cap_base_nt`` is the lazily-initialized capacity base for the row
        (host-owned mirror of the reference's ``added = capacity`` init,
        bucket.go:194-196).
      * ``created_ns`` is the host-owned creation timestamp (repo.go:205).
    """

    rows: jax.Array  # int32[K] bucket-slot indices
    now_ns: jax.Array  # int64[K] request clock (the injected-clock seam)
    freq: jax.Array  # int64[K] rate frequency (capacity in tokens)
    per_ns: jax.Array  # int64[K] rate period
    count_nt: jax.Array  # int64[K] tokens per request, in nanotokens
    nreq: jax.Array  # int64[K] identical requests coalesced into this row
    cap_base_nt: jax.Array  # int64[K] capacity base (0 ⇒ fresh bucket)
    created_ns: jax.Array  # int64[K] bucket creation time


class TakeResult(NamedTuple):
    """Per-row outcome. The host fans per-request responses out of this:
    request i (0-based arrival order) of a row with admitted count k gets
    ``ok = i < k`` and ``remaining = have − min(i+1, k)·count`` (the
    reference returns post-commit remaining on success, pre-reject remaining
    on failure, bucket.go:215-224)."""

    have_nt: jax.Array  # int64[K] tokens after refill, before the batch's takes
    admitted: jax.Array  # int64[K] how many of nreq were admitted
    own_added_nt: jax.Array  # int64[K] this node's PN lane after commit …
    own_taken_nt: jax.Array  # int64[K] … the exact lane values for the v2 trailer
    elapsed_ns: jax.Array  # int64[K] bucket elapsed after commit
    sum_added_nt: jax.Array  # int64[K] Σ lanes added post-commit … the aggregate
    sum_taken_nt: jax.Array  # int64[K] … scalars reference peers expect in the header


def take_batch(
    state: LimiterState, req: TakeRequest, node_slot: int
) -> tuple[LimiterState, TakeResult]:
    """Pure function: apply a microbatch of takes, return new state + results.

    Mirrors bucket.go:186-225 step-for-step on each row:
    capacity base (lazy init is host-side), monotonic-time guard
    (bucket.go:198-201), refill capped at capacity — cap may be negative,
    forfeiting excess tokens from merges (bucket.go:211-213) — and a
    conditional commit of (grant, taken, elapsed) only when at least one
    request is admitted (bucket.go:217-223).
    """
    i64 = jnp.int64
    rows = req.rows

    pn_rows = state.pn[rows]  # [K, N, 2] gather
    sum_added = pn_rows[:, :, ADDED].sum(axis=-1)
    sum_taken = pn_rows[:, :, TAKEN].sum(axis=-1)

    cap_now_nt = req.freq * NANO  # capacity of *this* request (bucket.go:192)
    tokens_nt = req.cap_base_nt + sum_added - sum_taken

    last = jnp.minimum(req.created_ns + state.elapsed[rows], req.now_ns)
    delta = req.now_ns - last

    # Refill: float64(delta)/float64(interval) tokens (bucket.go:130-148),
    # interval being the truncating integer division per/freq.
    safe_freq = jnp.where(req.freq == 0, 1, req.freq)
    interval = req.per_ns // safe_freq
    rate_zero = (req.freq == 0) | (req.per_ns == 0) | (interval == 0)
    safe_interval = jnp.where(interval == 0, 1, interval)
    grant_nt = jnp.where(rate_zero, 0, refill_grant_nt(delta, safe_interval))
    missing_nt = cap_now_nt - tokens_nt
    grant_nt = jnp.minimum(grant_nt, missing_nt)

    have_nt = tokens_nt + grant_nt

    # Greedy admission of nreq identical requests of count_nt each: the first
    # take sees the refilled balance; takes 2..n run at the same now (delta 0,
    # no further refill), so k = clip(have // count, 0, nreq).
    safe_count = jnp.where(req.count_nt <= 0, 1, req.count_nt)
    k = jnp.clip(have_nt // safe_count, 0, req.nreq)
    k = jnp.where(req.count_nt > 0, k, 0)
    success = k >= 1

    # Over-capacity forfeit, monotone form: the reference commits a NEGATIVE
    # grant when merges pushed tokens above capacity (bucket.go:211-213),
    # which would make the added-lane non-monotone — and any max-based join
    # (UDP merge or mesh max-convergence) would resurrect forfeited tokens
    # (the reference's own protocol has exactly that quirk). Booking the
    # forfeit as extra TAKEN keeps both lanes monotone G-counters with the
    # same observable balance: a − t is unchanged.
    forfeit = jnp.maximum(-grant_nt, i64(0))
    d_added = jnp.where(success, jnp.maximum(grant_nt, i64(0)), i64(0))
    d_taken = jnp.where(success, k * req.count_nt + forfeit, i64(0))
    d_elapsed = jnp.where(success, delta, i64(0))

    # Padding rows (nreq == 0) contribute zero deltas, so duplicate indices
    # from padding are harmless under scatter-add. The (added, taken) pair
    # commits as one scatter of two-element windows: TPU scatter cost is
    # per update, not per element (scripts/probe_scatter.py), so this
    # halves the pn commit versus two element-granular scatters.
    pair = jnp.stack([d_added, d_taken], axis=-1)
    pn = state.pn.at[rows, node_slot].add(pair)
    elapsed = state.elapsed.at[rows].add(d_elapsed)

    result = TakeResult(
        have_nt=have_nt,
        admitted=k,
        own_added_nt=pn_rows[:, node_slot, ADDED] + d_added,
        own_taken_nt=pn_rows[:, node_slot, TAKEN] + d_taken,
        elapsed_ns=state.elapsed[rows] + d_elapsed,
        sum_added_nt=sum_added + d_added,
        sum_taken_nt=sum_taken + d_taken,
    )
    return LimiterState(pn=pn, elapsed=elapsed), result


take_batch_jit = partial(jax.jit, static_argnames=("node_slot",), donate_argnums=0)(
    take_batch
)


def take_n_batch(
    state: LimiterState, packed: jax.Array, node_slot: int
) -> tuple[LimiterState, jax.Array]:
    """The take-n serving kernel: ONE packed ``int64[TAKE_PACK_ROWS, K]``
    request matrix in, ONE packed ``int64[TAKE_RESULT_ROWS, K]`` result
    matrix out — the exact transfer layout the feeder tick ships
    (engine._apply_takes), promoted to a certified kernel root of its
    own. Hot-key coalescing rides the ``nreq`` row: n same-(bucket,
    rate, count) takes collapse into one kernel row granting
    ``min(n, available)`` in a single dispatch, and the host splits the
    grant FIFO across the waiting tickets (:func:`split_grant`).

    The admission algebra is :func:`take_batch`'s — this wrapper only
    fixes the wire layout — but it is registered as its own prove root
    so the n>1 greedy grant is checked DIRECTLY against the sequential
    one-at-a-time replay (PTP002), the deny fixpoint is pinned (PTP003),
    and the packed layout's dtypes can't drift (PTP005)."""
    req = TakeRequest(
        rows=packed[0].astype(jnp.int32),
        now_ns=packed[1],
        freq=packed[2],
        per_ns=packed[3],
        count_nt=packed[4],
        nreq=packed[5],
        cap_base_nt=packed[6],
        created_ns=packed[7],
    )
    state, res = take_batch(state, req, node_slot)
    out = jnp.stack(
        [
            res.have_nt,
            res.admitted,
            res.own_added_nt,
            res.own_taken_nt,
            res.elapsed_ns,
            res.sum_added_nt,
            res.sum_taken_nt,
        ]
    )
    return state, out


take_n_batch_jit = partial(
    jax.jit, static_argnames=("node_slot",), donate_argnums=0
)(take_n_batch)


def split_grant(
    have_nt: int, admitted: int, count_nt: int, nreq: int
) -> list[tuple[int, bool]]:
    """Deterministic FIFO split of one coalesced row's grant across its
    ``nreq`` waiting tickets, in arrival order: the first ``admitted``
    tickets succeed (each seeing the balance after its own commit), the
    rest get clean denies (each seeing the balance after ALL admitted
    commits). This is host policy — the kernel only reports ``admitted``
    — so it is registered as its own prove root: the small-domain model
    checks the split against the first-k-of-m sequential outcome
    bit-exactly (a LIFO or round-robin split is rejected as PTP002)."""
    return [
        remaining_for_request(have_nt, admitted, count_nt, i)
        for i in range(nreq)
    ]


def remaining_for_request(
    have_nt: int, admitted: int, count_nt: int, index: int
) -> tuple[int, bool]:
    """Host-side fan-out of one coalesced row to per-request responses.

    ``index`` is the request's 0-based arrival position in the coalesced
    queue. Matches the reference's sequential semantics: admitted requests
    see the balance after their own commit; rejected ones see the balance
    left after all admitted requests (bucket.go:215-224). The uint64 cast of
    the reference is clamped at zero (PN merges can drive the balance
    negative; Go's negative-float→uint64 cast is UB we do not reproduce).
    """
    ok = index < admitted
    consumed = (index + 1 if ok else admitted) * count_nt
    remaining_nt = have_nt - consumed
    return max(remaining_nt, 0) // NANO, ok
