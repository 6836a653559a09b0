"""Mean time a take waits in the C++ HTTP front, from its parse to the
pump's poll that hands it to Python (``http_front_queue_ns``,
``net/native_http.py``), over the window."""


def read(run):
    return run.hist_mean_ms("http_front_queue_ns")
