"""Dense device-resident limiter state — the TPU replacement for the
reference's ``map[string]*Bucket`` + per-bucket mutex (repo.go:171-176,
bucket.go:20-32).

Design (SURVEY.md §7): rate-limit state is a join-semilattice and every
operation is branch-light arithmetic over a few scalars, which is
embarrassingly vectorizable. Instead of a hash map of locked structs, state
is a pair of dense int64 arrays:

* ``pn: int64[B, N, 2]`` — B bucket slots × N node slots × (ADDED, TAKEN)
  in fixed-point *nanotokens* (1 token = 1e9 nanotokens). This is a true
  PN-counter: node ``i`` only ever increments its own ``pn[:, i, :]`` lane;
  remote lanes change only by elementwise max-merge. Bucket value =
  ``capacity + Σadded − Σtaken``. This supersedes the reference's lossy
  scalar max-merge (bucket.go:240-263) where concurrent takes on different
  nodes could be silently dropped.
* ``elapsed: int64[B]`` — per-bucket G-counter of nanoseconds consumed by
  successful takes (bucket.go:28-29), merged by max.

Everything *not* replicated stays on the host, owned by the bucket
directory: the name→row mapping, per-row ``created`` timestamps
(bucket.go:30-31 — deliberately local, the clock-skew-independence trick)
and the lazily-initialized capacity base (bucket.go:194-196). int64
fixed-point makes the max-merge bit-deterministic across replicas, which
float64 on mixed hardware would not be.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

NANO = 1_000_000_000

ADDED = 0  # pn[..., ADDED]: granted refills + nothing else
TAKEN = 1  # pn[..., TAKEN]: successfully taken tokens


class LimiterState(NamedTuple):
    """The replicated CRDT planes. A pytree; every field is a jax Array."""

    pn: jax.Array  # int64[B, N, 2] nanotokens
    elapsed: jax.Array  # int64[B] nanoseconds

    @property
    def num_buckets(self) -> int:
        return self.pn.shape[0]

    @property
    def num_nodes(self) -> int:
        return self.pn.shape[1]


@dataclasses.dataclass(frozen=True)
class LimiterConfig:
    """Shape configuration for a limiter instance.

    ``buckets`` is the pre-allocated bucket-slot pool (the reference grows a
    map dynamically, repo.go:200-207; XLA wants static shapes, so the
    directory allocates rows out of this pool). ``nodes`` bounds cluster
    size — one PN lane per node.
    """

    buckets: int = 4096
    nodes: int = 8

    def hbm_bytes(self) -> int:
        return self.buckets * self.nodes * 2 * 8 + self.buckets * 8


# The north-star scale from BASELINE.json: 1M buckets × 256 node slots.
FLAGSHIP = LimiterConfig(buckets=1_000_000, nodes=256)

# A small config for tests and single-host deployments.
SMALL = LimiterConfig(buckets=1024, nodes=8)


def init_state(config: LimiterConfig, device=None) -> LimiterState:
    """Zero state: every bucket empty, which reads as full-at-capacity on
    first take (value = capacity + 0 − 0), matching the reference's lazy
    capacity init (bucket.go:194-196).

    ``device`` is a device or sharding for both planes, or a
    ``LimiterState`` of one per plane. The zeros are made where they
    live: a sharded state never passes through one device (at 4M x 256
    the whole state is 16.4 GB, more than one v5e holds)."""
    per_plane = device if isinstance(device, LimiterState) else LimiterState(device, device)
    return LimiterState(
        pn=jnp.zeros((config.buckets, config.nodes, 2), jnp.int64, device=per_plane.pn),
        elapsed=jnp.zeros((config.buckets,), jnp.int64, device=per_plane.elapsed),
    )
