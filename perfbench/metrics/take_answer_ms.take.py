"""Mean time from a take's completion by the engine to its answer being
handed back to the C++ front with its pump batch
(``http_front_answer_ns``, ``net/native_http.py``), over the window."""


def read(run):
    return run.hist_mean_ms("http_front_answer_ns")
