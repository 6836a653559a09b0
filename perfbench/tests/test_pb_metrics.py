"""The per-layer readers of the seam, the take fold and the send path,
on a synthetic run: what each reads from the ``/debug/vars`` snapshots
that bracket the window, and that each reads nothing, without raising,
from a node that does not publish its numbers."""

import pytest

from perfbench.run import Run, load_metric_reader


def _vars(mono_ns, hists, **counters):
    return {
        "mono_ns": mono_ns,
        "histograms": {n: {"count": c, "sum": s} for n, (c, s) in hists.items()},
        **counters,
    }


def _run(traced=False):
    r = Run()
    r.vars0 = _vars(
        1_000_000_000,
        {
            "http_front_queue_ns": (10, 5_000_000),
            "http_front_submit_ns": (10, 1_000_000),
            "http_front_answer_ns": (10, 2_000_000),
            "take_queue_ns": (10, 3_000_000),
            "take_service_ns": (10, 9_000_000),
        },
        take_rows_dispatched=4,
        repl_send_ns=100_000_000,
    )
    end = _vars(
        3_000_000_000,
        {
            "http_front_queue_ns": (110, 105_000_000),
            "http_front_submit_ns": (110, 21_000_000),
            "http_front_answer_ns": (110, 52_000_000),
            "take_queue_ns": (110, 153_000_000),
            "take_service_ns": (130, 309_000_000),
        },
        take_rows_dispatched=84,
        repl_send_ns=600_000_000,
    )
    if traced:
        # A traced run reads the window before the trace.
        r.trace_vars0, r.trace_vars1 = end, _vars(9_000_000_000, {})
        r.vars1 = _vars(9_000_000_000, {})
    else:
        r.vars1 = end
    return r


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize(
    "name, want",
    [
        ("front_queue_ms.take", 1.0),
        ("front_submit_ms.take", 0.2),
        ("take_answer_ms.take", 0.5),
        ("take_queue_ms.take", 1.5),
        ("fold_ratio_packed.take", 120 / 80),
        ("repl_send_share.take", 25.0),
    ],
)
def test_reader(name, want, traced):
    assert load_metric_reader(name)(_run(traced)) == pytest.approx(want)


@pytest.mark.parametrize(
    "name",
    [
        "front_queue_ms.take",
        "front_submit_ms.take",
        "take_answer_ms.take",
        "take_queue_ms.take",
        "fold_ratio_packed.take",
        "repl_send_share.take",
    ],
)
def test_reader_of_a_node_without_the_numbers_reads_nothing(name):
    r = Run()
    r.vars0 = {"histograms": {"take_service_ns": {"count": 1, "sum": 5}}}
    r.vars1 = {"histograms": {"take_service_ns": {"count": 9, "sum": 50}}}
    assert load_metric_reader(name)(r) is None
