"""Mean wait of a take at the seam between the C++ HTTP front and the
engine (``http_front_wait_ns``, ``net/native_http.py``) over the window:
the difference of the histogram's sum and count between the snapshots
of ``/debug/vars`` that bracket the window."""


def read(run):
    return run.hist_mean_ms("http_front_wait_ns")
