"""Share of the window the node's rx thread spends walking raw delta
datagrams: the summed wall time of the dv2 structure walk
(``rx_walk_ns``, ``ops/ingest.py`` ``walk``, on the native path or the
numpy one) over the window's length on the node's own clock
(``mono_ns``), in %. A node that does not publish ``rx_walk_ns`` reads
nothing."""


def read(run):
    close = run.trace_vars0 or run.vars1  # the window's end, as run.counter reads it
    span = run.counter("mono_ns")
    if "rx_walk_ns" not in close or span <= 0:
        return None
    return 100.0 * run.counter("rx_walk_ns") / span
