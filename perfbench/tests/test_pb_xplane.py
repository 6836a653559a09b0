"""The trace reduction: busy time, kernel time and idle gaps."""

import os
import subprocess
import sys

import pytest

from perfbench import roofline, xplane
from perfbench.xplane import Event

DEV = "/device:TPU:0"


def _synthetic():
    return [
        Event("/host:CPU", "python", "patrol.take_packed", 0, 10),
        Event(DEV, "XLA Modules", "jit_step", 20, 100),
        Event(DEV, "XLA Ops", "gather", 20, 60),
        Event(DEV, "XLA Ops", "scatter", 70, 50),  # overlaps the gather by 10
        Event("/host:CPU", "python", "patrol.decode_fold_raw", 200, 10),
        Event(DEV, "XLA Modules", "jit_decode_fold_raw", 300, 200),
        Event(DEV, "XLA Ops", "fusion", 300, 200),
        Event("/host:CPU", "python", "poll", 500, 400),
    ]


def test_busy_is_the_union_of_device_ops():
    evs = _synthetic()
    assert xplane.busy_ns(xplane.device_events(evs)[DEV]) == 100 + 200


def test_kernel_time_by_launching_annotation():
    k = xplane.kernel_ns(_synthetic())
    assert k == {"take_packed": 100, "decode_fold_raw": 200}


def test_reduce_and_gaps():
    r = xplane.reduce(_synthetic(), 1000)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1e-6)
    name, length = r["idle_gaps"][0]
    assert name == "poll" and length == pytest.approx(520e-9)  # window [20, 1020)
    assert r["device_ops"][0] == ("decode_fold_raw: fusion", pytest.approx(200e-9))


def test_no_device_events_reads_nothing():
    assert xplane.reduce([Event("/host:CPU", "python", "x", 0, 5)], 100) == {}


RECORD = r"""
import sys, jax, jax.numpy as jnp
jax.profiler.start_trace(sys.argv[1])
f = jax.jit(lambda x: (x * 2.0).sum())
x = jnp.ones((512, 512))
f(x).block_until_ready()
for _ in range(5):
    with jax.profiler.TraceAnnotation("patrol.double_sum"):
        f(x).block_until_ready()
jax.profiler.stop_trace()
"""


def test_recorded_cpu_trace(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", RECORD, str(tmp_path)], check=True, env=env, timeout=120)
    path = xplane.find_xplane(str(tmp_path))
    evs = xplane.load_events(path)
    dev = xplane.device_events(evs)["cpu"]
    # Known busy time: the same events' union, computed by brute force.
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns) for e in dev)
    covered, cur_s, cur_e = 0, None, None
    for s, e in spans:
        if cur_e is None or s > cur_e:
            covered += 0 if cur_e is None else cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    covered += cur_e - cur_s
    assert xplane.busy_ns(dev) == covered > 0
    k = xplane.kernel_ns(evs)
    assert k.get("double_sum", 0) > 0
    # Known kernel time: the device events that began inside one of the
    # annotations (each call is launched and awaited inside its own).
    spans = [(a.start_ns, a.start_ns + a.dur_ns) for a in xplane.annotations(evs)]
    inside = sum(e.dur_ns for e in dev if any(s <= e.start_ns <= t for s, t in spans))
    assert k["double_sum"] == inside
    r = xplane.reduce(evs, 10**9)
    assert 0 < r["busy_s"] < r["window_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_roofline_counts_useful_rows_only():
    assert roofline.take_bytes(0, 256) == 0
    assert roofline.take_bytes(10, 256) == 10 * (256 * 16 + 152)
    # The least time at 819 GB/s for 819 MB is 1 ms: a 2 ms kernel is at 50%.
    assert roofline.share(819_000_000, 2e-3, "TPU v5 lite") == pytest.approx(50.0)


def test_generic_step_programs_follow_their_enqueue_to_the_annotation():
    evs = [
        Event("/host:CPU", "python", "patrol.take_packed", 100, 50),
        Event("/host:CPU", "python", "patrol.commit_blocks", 200, 50),
        Event("/host:CPU", "queue", xplane.ENQUEUE, 120, 1, 7),  # inside take_packed
        Event("/host:CPU", "queue", xplane.ENQUEUE, 210, 1, 8),  # inside commit_blocks
        # Both programs start on the device after both annotations began.
        Event(DEV, "XLA Modules", "jit_step(1)", 300, 40, 7),
        Event(DEV, "XLA Modules", "jit_step(2)", 340, 60, 8),
        Event(DEV, "XLA Modules", "jit_read_rows(3)", 400, 10, 9),
    ]
    assert xplane.kernel_ns(evs) == {"take_packed": 40, "commit_blocks": 60, "read_rows": 10}


def test_short_op_names():
    name = ('%custom-call.1 = u32[1000000,256,2]{1,2,0:T(2,128)} custom-call(s64[1000000,256,2]'
            '{1,2,0:T(2,128)} %state_pn.1), custom_call_target="X64SplitLow"')
    assert xplane.short_op(name) == "%custom-call.1 u32[1000000,256,2] X64SplitLow"
    assert xplane.short_op("fusion.3") == "fusion.3"
