"""The take kernel's share of its HBM roofline over the traced window.

Kernel time is the device time of the programs launched under the
``patrol.take_packed`` annotation. Bytes are ``roofline.take_bytes`` of
the take rows queued during the trace (tickets less folded tickets,
from the snapshots bracketing the trace) at the node's lane count. The
kernel works in int64, for which no peak is published, so only the
byte bound applies."""

from perfbench import roofline


def read(run):
    tickets, _ = run.hist("take_service_ns", traced=True)
    rows = tickets - run.counter("take_tickets_folded", traced=True)
    lanes = int(run.config["node"]["node_lanes"])
    return run.roofline_share(roofline.take_bytes(rows, lanes), ["take_packed"])
