"""The per-layer readers of a device take's drain → complete stages, the
feeder's CPU share and the Python threads' CPU, on a synthetic run: what
each reads from the ``/debug/vars`` snapshots that bracket the window,
that a window without tickets reads nothing, and that each reads nothing,
without raising, from a node that does not publish its numbers."""

import pytest

from perfbench.run import Run, load_metric_reader


def _vars(mono_ns, hists, **counters):
    return {
        "mono_ns": mono_ns,
        "histograms": {n: {"count": c, "sum": s} for n, (c, s) in hists.items()},
        **counters,
    }


STAGES = ("take_prep", "take_lock_wait", "take_dispatch", "take_handoff", "take_readback")


def _stage_run(traced=False, n1=110):
    """A window of 100 device take tickets (``n1`` - 10) whose five
    stages sum to 2, 1, 0.5, 1.5 and 0.25 ms a ticket; the feeder busy
    4 s, 3 s of it its own CPU; the Python threads 5 s of CPU in 2 s."""
    n = n1 - 10
    per_ms = (2.0, 1.0, 0.5, 1.5, 0.25)
    r = Run()
    r.vars0 = _vars(
        1_000_000_000,
        {f"{s}_ns": (10, 7_000_000) for s in STAGES},
        feeder_busy_ns=500_000_000,
        feeder_cpu_ns=100_000_000,
        py_threads_cpu_ns=9_000_000_000,
    )
    end = _vars(
        3_000_000_000,
        {f"{s}_ns": (n1, 7_000_000 + int(n * ms * 1e6)) for s, ms in zip(STAGES, per_ms)},
        feeder_busy_ns=4_500_000_000,
        feeder_cpu_ns=3_100_000_000,
        py_threads_cpu_ns=14_000_000_000,
    )
    if traced:
        r.trace_vars0, r.trace_vars1 = end, _vars(9_000_000_000, {})
        r.vars1 = _vars(9_000_000_000, {})
    else:
        r.vars1 = end
    return r


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize(
    "name, want",
    [
        ("take_prep_ms.take", 2.0),
        ("take_lock_wait_ms.take", 1.0),
        ("take_dispatch_ms.take", 0.5),
        ("take_handoff_ms.take", 1.5),
        ("take_readback_ms.take", 0.25),
        ("feeder_cpu_share.take", 75.0),
        ("py_threads_cpu_share.take", 250.0),
    ],
)
def test_stage_and_thread_reader(name, want, traced):
    assert load_metric_reader(name)(_stage_run(traced)) == pytest.approx(want)


@pytest.mark.parametrize("name", [f"{s}_ms.take" for s in STAGES])
def test_stage_reader_of_a_window_without_tickets_reads_nothing(name):
    assert load_metric_reader(name)(_stage_run(n1=10)) is None


@pytest.mark.parametrize(
    "name",
    [f"{s}_ms.take" for s in STAGES] + ["feeder_cpu_share.take", "py_threads_cpu_share.take"],
)
def test_stage_reader_of_a_node_without_the_numbers_reads_nothing(name):
    # The parent node: none of the five histograms, the feeder counters
    # or the threads' CPU.
    r = Run()
    r.vars0 = _vars(1_000_000_000, {"take_service_ns": (1, 5)})
    r.vars1 = _vars(3_000_000_000, {"take_service_ns": (9, 50)})
    assert load_metric_reader(name)(r) is None


def test_thread_cpu_lists_the_engine_threads_and_only_grows(monkeypatch):
    from patrol_tpu.models.limiter import LimiterConfig
    from patrol_tpu.ops.rate import Rate
    from patrol_tpu.runtime import engine as engine_mod
    from patrol_tpu.utils import profiling

    # Every take rides the feeder, so its thread has CPU to show.
    monkeypatch.setattr(engine_mod, "HOST_FASTPATH", False)
    eng = engine_mod.DeviceEngine(LimiterConfig(buckets=64, nodes=4), node_slot=0)
    try:
        before = profiling.thread_cpu_ns()
        for i in range(20):
            eng.take(f"cpu{i}", Rate(freq=10, per_ns=1_000_000_000), 1)
        after = profiling.thread_cpu_ns()
    finally:
        eng.stop()
    for name in ("patrol-engine", "patrol-engine-complete", "MainThread"):
        assert name in before and name in after, name
    for name, ns in before.items():
        if name in after:
            assert after[name] >= ns > 0, name
    assert after["patrol-engine"] > before["patrol-engine"]
