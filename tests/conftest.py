"""Test configuration: force an 8-device virtual CPU mesh before JAX loads.

Multi-chip hardware is unavailable in CI; sharding tests run on
``--xla_force_host_platform_device_count=8`` CPU devices, mirroring how the
driver dry-runs the multi-chip path. This must happen before any module
imports jax.
"""

import os
import re

# Hard override: unit/integration tests always run on the virtual CPU
# mesh, whatever the environment pins; the chip is driven by chip_smoke.py
# and bench.py (tests/test_tpu_compile.py compiles for a DESCRIBED chip).
os.environ["JAX_PLATFORMS"] = "cpu"

# patrol-fleet metrics gossip stays MANUALLY paced under test: the chaos
# suite's seeded faultnet streams are per-link packet-for-packet
# deterministic, and a background 1 Hz gossip flusher interleaving extra
# datagrams would consume rng draws at wall-clock-dependent points and
# un-seed the schedules. Gossip behavior itself is covered by
# tests/test_fleet.py, which drives plane.flush() explicitly (and one
# test exercises the real flusher thread with a tight interval).
os.environ.setdefault("PATROL_FLEET_GOSSIP_MS", "0")
# patrol-audit stays MANUALLY paced under test for the same reason: a
# background audit flusher would interleave extra control datagrams into
# the chaos suite's seeded per-link faultnet streams and un-seed the
# schedules. Audit behavior is covered by tests/test_audit.py, which
# drives plane.flush() explicitly. The admitted-token window likewise
# closes manually (roll(force=True)) so frozen-clock differentials stay
# deterministic.
os.environ.setdefault("PATROL_AUDIT_MS", "0")
os.environ.setdefault("PATROL_AUDIT_WINDOW_MS", "0")
# Bucket-lifecycle GC likewise stays MANUALLY paced under test: the
# feeder's window-rollover sweep observes the injected clock at
# wall-clock-dependent ticks, so a seeded differential run (fastpath vs
# device, chaos schedules) would reclaim-and-recreate buckets at
# nondeterministic points — flipping `created` flags and incast traffic
# between runs. Lifecycle behavior itself is covered by
# tests/test_lifecycle.py (and the chaos GC suite), which drive
# engine.gc_sweep() / configure_lifecycle() explicitly.
os.environ.setdefault("PATROL_GC_WINDOW_MS", "0")
_flags = os.environ.get("XLA_FLAGS", "")
_m = re.search(r"--xla_force_host_platform_device_count=(\d+)", _flags)
if _m is None or int(_m.group(1)) < 8:
    _flags = re.sub(r"--xla_force_host_platform_device_count=\d+\s*", "", _flags)
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Pin the config too, before any backend initializes: a jax already
# imported by a plugin would not re-read the environment.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
