"""Share of the window the node spends in its replication send path:
the summed durations of its delta-plane flushes and state fan-outs
(``repl_send_ns``, ``net/delta.py``, ``net/native_replication.py``) over
the window's length on the node's own clock (``mono_ns``), in %."""


def read(run):
    span = run.counter("mono_ns")
    return 100.0 * run.counter("repl_send_ns") / span if span > 0 else None
