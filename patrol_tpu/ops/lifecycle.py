"""Bucket lifecycle kernel — the vectorized IsZero predicate that makes
idle-bucket GC safe (ROADMAP item 4; the reference's ``Bucket.IsZero``
insight, bucket.go's full-bucket reconstruction property).

A limiter bucket is *reconstructible from its rate* exactly when its
reconstructed balance at ``now`` — tokens plus the refill grant the next
take would commit — equals its capacity. Dropping such a bucket and
lazily re-creating it later is observation-equivalent: the very first
take against the fresh row sees the same ``have``/``admitted``/
``remaining`` the old row would have produced, because the old row's
entire history is subsumed by "full at capacity". Cold state can
therefore be *dropped*, not archived (zero lanes are the join's bottom
element, delta-mutation CRDTs arXiv:1410.2803, so later state re-enters
exactly through the max-lattice join).

The refill arithmetic below mirrors :func:`patrol_tpu.ops.take.take_batch`
**step for step** (float64 grant, floor quantization, capacity clamp):
the predicate must agree bit-for-bit with what the take kernel would
grant, or a "full" verdict could reclaim a bucket whose next take would
have seen less than capacity — an admitted-token loss. That conservation
law (plus time-monotonicity of the verdict and join-re-entry exactness)
is machine-checked by the ``lifecycle_iszero`` model suite declared with
this kernel's ``PROVE_ROOTS`` entry (patrol_tpu/ops/obligations.py).

What the engine keeps when it reclaims: the bucket's OWN PN lane (and
its refill clock: ``elapsed``/``created``) goes into a compact host-side
tombstone (runtime/directory.py) and re-seeds the row on re-creation.
A row that holds any nonzero REMOTE lane is never reclaimable: those
lanes are acknowledged writes of other nodes, which their writers do
not ship again, and a partial join can read full (a lane with positive
balance landed before the lanes that spend it) — dropping it would lose
the spend for good. The probe therefore returns the own-lane values
next to the verdict so the sweep reads each candidate exactly once.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from patrol_tpu.models.limiter import ADDED, TAKEN, NANO, LimiterState
from patrol_tpu.ops.take import _GRANT_CLIP, refill_grant_nt


class LifecycleProbe(NamedTuple):
    """A microbatch of K reclaim-candidate probes. Padding rows carry
    ``cap_base_nt == 0`` (capacity unknown ⇒ never reclaimable), so any
    row index is safe padding — the gather is read-only."""

    rows: jax.Array  # int32[K] bucket-slot indices
    now_ns: jax.Array  # int64[K] sweep clock (the injected-clock seam)
    per_ns: jax.Array  # int64[K] rate period (0 ⇒ unknown: no projection)
    cap_base_nt: jax.Array  # int64[K] capacity base (0 ⇒ not reclaimable)
    created_ns: jax.Array  # int64[K] bucket creation time


class LifecycleView(NamedTuple):
    """Per-candidate verdict plus the tombstone payload (one gather)."""

    full: jax.Array  # bool[K]  reconstructed value == capacity
    own_added_nt: jax.Array  # int64[K] this node's PN lane …
    own_taken_nt: jax.Array  # int64[K] … the tombstone residue
    elapsed_ns: jax.Array  # int64[K] the bucket's refill clock


def lifecycle_probe(
    state: LimiterState, probe: LifecycleProbe, node_slot: int
) -> LifecycleView:
    """Pure read: evaluate the IsZero predicate over a probe batch.

    A bucket is full (reclaimable) iff the refill grant the next take
    would compute covers the distance to capacity — the exact expression
    (and operation order) of ``take_batch``'s grant path, including the
    over-capacity case (``missing <= 0``: merges pushed tokens past
    capacity; the next take forfeits down to capacity, so the row is
    reconstruction-equivalent to a fresh full bucket too). A zero or
    unknown rate projects no grant, so such rows reclaim only when the
    standing balance already covers capacity — conservative, never a
    token lost. A row with a nonzero remote lane is never full (module
    docstring).
    """
    rows = probe.rows

    pn_rows = state.pn[rows]  # [K, N, 2] gather
    sum_added = pn_rows[:, :, ADDED].sum(axis=-1)
    sum_taken = pn_rows[:, :, TAKEN].sum(axis=-1)
    tokens_nt = probe.cap_base_nt + sum_added - sum_taken

    elapsed = state.elapsed[rows]
    last = jnp.minimum(probe.created_ns + elapsed, probe.now_ns)
    delta = probe.now_ns - last

    freq = probe.cap_base_nt // NANO
    safe_freq = jnp.where(freq == 0, 1, freq)
    interval = probe.per_ns // safe_freq
    rate_zero = (freq == 0) | (probe.per_ns == 0) | (interval == 0)
    safe_interval = jnp.where(interval == 0, 1, interval)
    grant_nt = jnp.where(rate_zero, 0, refill_grant_nt(delta, safe_interval))

    missing_nt = probe.cap_base_nt - tokens_nt
    lane = jnp.arange(pn_rows.shape[1])
    remote = jnp.any((pn_rows != 0).any(axis=-1) & (lane != node_slot), axis=-1)
    full = (probe.cap_base_nt > 0) & (grant_nt >= missing_nt) & ~remote
    return LifecycleView(
        full=full,
        own_added_nt=pn_rows[:, node_slot, ADDED],
        own_taken_nt=pn_rows[:, node_slot, TAKEN],
        elapsed_ns=elapsed,
    )


# NOT donated: the probe is a pure read — the engine holds _state_mu for
# the call but the state buffers stay live for the next tick.
lifecycle_probe_jit = partial(jax.jit, static_argnames=("node_slot",))(
    lifecycle_probe
)


def host_lifecycle_full(
    sum_added_nt,
    sum_taken_nt,
    elapsed_ns,
    cap_base_nt,
    created_ns,
    now_ns,
    per_ns,
    remote=False,
) -> np.ndarray:
    """Numpy reference twin of the kernel's verdict, for host-resident
    lanes (the fast-path buckets GC evaluates under ``_host_mu`` without
    a device hop) and for tests. Same expressions, same operation order —
    any divergence from the kernel is a bug the lifecycle tests pin.
    ``remote``: the row holds a nonzero lane of another node (never
    full)."""
    sum_added_nt = np.asarray(sum_added_nt, np.int64)
    sum_taken_nt = np.asarray(sum_taken_nt, np.int64)
    elapsed_ns = np.asarray(elapsed_ns, np.int64)
    cap_base_nt = np.asarray(cap_base_nt, np.int64)
    created_ns = np.asarray(created_ns, np.int64)
    per_ns = np.asarray(per_ns, np.int64)

    tokens_nt = cap_base_nt + sum_added_nt - sum_taken_nt
    last = np.minimum(created_ns + elapsed_ns, now_ns)
    delta = now_ns - last

    freq = cap_base_nt // NANO
    safe_freq = np.where(freq == 0, 1, freq)
    interval = per_ns // safe_freq
    rate_zero = (freq == 0) | (per_ns == 0) | (interval == 0)
    safe_interval = np.where(interval == 0, 1, interval)
    grant_f = np.where(
        rate_zero,
        0.0,
        delta.astype(np.float64) / safe_interval.astype(np.float64) * float(NANO),
    )
    grant_nt = np.floor(np.clip(grant_f, 0.0, _GRANT_CLIP)).astype(np.int64)
    missing_nt = cap_base_nt - tokens_nt
    return (cap_base_nt > 0) & (grant_nt >= missing_nt) & ~np.asarray(remote)


def host_reconstructed_nt(
    sum_added_nt,
    sum_taken_nt,
    elapsed_ns,
    cap_base_nt,
    created_ns,
    now_ns,
    per_ns,
) -> np.ndarray:
    """The reconstructed balance at ``now`` — ``have_nt`` exactly as the
    next take would compute it (refill capped at capacity, over-capacity
    forfeited). The soak gate's per-bucket digest field: a GC'd bucket
    reconstructs to capacity by the IsZero contract, and a no-GC
    reference run's live row must reconstruct to the same value."""
    sum_added_nt = np.asarray(sum_added_nt, np.int64)
    sum_taken_nt = np.asarray(sum_taken_nt, np.int64)
    elapsed_ns = np.asarray(elapsed_ns, np.int64)
    cap_base_nt = np.asarray(cap_base_nt, np.int64)
    created_ns = np.asarray(created_ns, np.int64)
    per_ns = np.asarray(per_ns, np.int64)

    tokens_nt = cap_base_nt + sum_added_nt - sum_taken_nt
    last = np.minimum(created_ns + elapsed_ns, now_ns)
    delta = now_ns - last
    freq = cap_base_nt // NANO
    safe_freq = np.where(freq == 0, 1, freq)
    interval = per_ns // safe_freq
    rate_zero = (freq == 0) | (per_ns == 0) | (interval == 0)
    safe_interval = np.where(interval == 0, 1, interval)
    grant_f = np.where(
        rate_zero,
        0.0,
        delta.astype(np.float64) / safe_interval.astype(np.float64) * float(NANO),
    )
    grant_nt = np.floor(np.clip(grant_f, 0.0, _GRANT_CLIP)).astype(np.int64)
    grant_nt = np.minimum(grant_nt, cap_base_nt - tokens_nt)
    return tokens_nt + grant_nt
