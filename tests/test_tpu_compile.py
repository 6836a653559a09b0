"""The served kernels, compiled for a described TPU v5e at real widths.

No chip is attached here: the TPU compiler builds each program for a
described ``v5e:2x2`` topology and refuses what the chip would refuse —
a kernel it cannot lower, a program that does not fit the device's
memory. Nothing runs, so these say nothing about results or time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the suite's workers
import every test file.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import patrol_tpu  # noqa: F401  (x64)
from patrol_tpu.models.limiter import FLAGSHIP, LimiterState
from patrol_tpu.ops import ingest as ingest_ops

HBM_BYTES = 15.75 * 2**30  # what one v5e chip gives a program
B, N = FLAGSHIP.buckets, FLAGSHIP.nodes  # 1M buckets x 256 lanes
K_TAKE = 64  # take rows: same program at every K, a quicker compile
K_MERGE = 8192  # the engine's MAX_MERGE_ROWS block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def state_spec(sharding, buckets=B) -> LimiterState:
    return LimiterState(
        pn=spec((buckets, N, 2), jnp.int64, sharding),
        elapsed=spec((buckets,), jnp.int64, sharding),
    )


def fits(compiled) -> int:
    """Bytes one device needs for the program (arguments aliased into
    outputs count once); asserts it fits one chip's HBM."""
    m = compiled.memory_analysis()
    need = (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert need <= HBM_BYTES, f"{need / 2**30:.2f} GiB > {HBM_BYTES / 2**30:.2f} GiB"
    return need


def test_take_packed(one_chip):
    from patrol_tpu.runtime.engine import _jit_take_packed

    compiled = (
        _jit_take_packed(0)
        .lower(state_spec(one_chip), spec((8, K_TAKE), jnp.int64, one_chip))
        .compile()
    )
    fits(compiled)
    # The refill is integer arithmetic: no float64 left for the TPU to
    # emulate (the emulation is what differed from the host twin).
    assert "f64" not in compiled.as_text()


def test_merge_packed(one_chip):
    from patrol_tpu.runtime.engine import _jit_merge_folded

    compiled = (
        _jit_merge_folded()
        .lower(state_spec(one_chip), spec((6, K_MERGE), jnp.int64, one_chip))
        .compile()
    )
    fits(compiled)


def test_commit_packed(one_chip):
    from patrol_tpu.runtime.engine import _jit_commit_packed

    compiled = (
        _jit_commit_packed()
        .lower(state_spec(one_chip), spec((6, 4, K_MERGE), jnp.int64, one_chip))
        .compile()
    )
    fits(compiled)


def test_merge_rows_dense(one_chip):
    from patrol_tpu.runtime.engine import _jit_merge_rows_dense

    k = 64
    compiled = (
        _jit_merge_rows_dense()
        .lower(
            state_spec(one_chip),
            spec((k,), jnp.int64, one_chip),
            spec((k, N, 2), jnp.int64, one_chip),
            spec((k,), jnp.int64, one_chip),
        )
        .compile()
    )
    fits(compiled)


def test_decode_fold_raw(one_chip):
    # The program is the same at every plane count; its compile grows
    # faster than linearly (64 planes ~2.5 s, 512 ~66 s here).
    p = 64
    row = ingest_ops.RAW_PLANE_BYTES
    e = ingest_ops.max_entries(row)
    compiled = ingest_ops.decode_fold_raw_jit.lower(
        state_spec(one_chip),
        spec((p, row), jnp.uint8, one_chip),
        spec((p,), jnp.int32, one_chip),
        spec((p, e), jnp.int32, one_chip),
        spec((p, e), jnp.int32, one_chip),
        spec((p, e), jnp.bool_, one_chip),
    ).compile()
    fits(compiled)


@pytest.mark.parametrize("replicas,buckets", [(1, 4 * B), (2, B)])
def test_mesh_step_four_chips(topo, replicas, buckets):
    """The fused merge+take+converge step over 4 described chips: one
    replica over 4 bucket shards at 4M buckets (1M per chip), or 2
    replicas (the tree converge) over 2 shards at 1M. The converge holds
    four shard-sized temporaries, so 2 replicas at 2M (19.08 GiB per
    chip) do not fit a v5e."""
    from patrol_tpu.parallel import topology as tp

    mesh = tp.make_mesh(replicas=replicas, devices=list(topo.devices))
    shards = mesh.shape[tp.BUCKET_AXIS]
    assert shards == 4 // replicas
    sh = tp.state_sharding(mesh)
    state = LimiterState(
        pn=spec((buckets, N, 2), jnp.int64, sh.pn),
        elapsed=spec((buckets,), jnp.int64, sh.elapsed),
    )
    mat = tp.batch_sharding(mesh)
    k = 8 * 4  # 8 rows per (replica, shard) block
    step = tp.build_cluster_step_packed(mesh, 0)
    compiled = step.lower(
        state, spec((8, k), jnp.int64, mat), spec((5, k), jnp.int64, mat)
    ).compile()
    fits(compiled)
    # Each device's arguments are its shard of the state, not all of it.
    share = buckets // shards * (N * 2 + 1) * 8
    assert compiled.memory_analysis().argument_size_in_bytes < 1.01 * share
