"""Mean time from the pump's poll return to the take's engine ticket
being created (``http_front_submit_ns``, ``net/native_http.py``): name
decoding, the directory pass and the host fast path, over the window."""


def read(run):
    return run.hist_mean_ms("http_front_submit_ns")
