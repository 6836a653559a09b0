"""The delta plane's rx walk reader, ``rx_walk_share.take``, on synthetic
runs: the share it reads from the ``/debug/vars`` snapshots that bracket
the window, and nothing, without raising, from a node that does not
publish ``rx_walk_ns``."""

import pytest

from perfbench.run import Run, load_metric_reader

READ = load_metric_reader("rx_walk_share.take")


def _vars(mono_ns, **counters):
    return {"mono_ns": mono_ns, "histograms": {}, **counters}


@pytest.mark.parametrize("traced", [False, True])
def test_reads_the_walk_share_of_the_window(traced):
    r = Run()
    r.vars0 = _vars(1_000_000_000, rx_walk_ns=5_000_000)
    end = _vars(3_000_000_000, rx_walk_ns=25_000_000)
    if traced:
        # A traced run reads the window before the trace.
        r.trace_vars0, r.trace_vars1 = end, _vars(9_000_000_000, rx_walk_ns=9 * 10**9)
        r.vars1 = _vars(9_000_000_000, rx_walk_ns=9 * 10**9)
    else:
        r.vars1 = end
    assert READ(r) == pytest.approx(1.0)


@pytest.mark.parametrize(
    "v0, v1",
    [
        # A node from before the walk was counted: a clock, no counter.
        (_vars(1_000_000_000, repl_send_ns=1), _vars(3_000_000_000, repl_send_ns=9)),
        # A node that publishes neither.
        ({"histograms": {}}, {"histograms": {}}),
    ],
)
def test_node_without_the_counter_reads_nothing(v0, v1):
    r = Run()
    r.vars0, r.vars1 = v0, v1
    assert READ(r) is None
