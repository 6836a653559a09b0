"""Mean time from the take dispatch's return to the completer starting
that tick's completion (``take_handoff_ns``, ``runtime/engine.py``): the
lock's release, the completion queue's back-pressure and the completer's
wake, per device take ticket, over the window. A node that does not
publish the histogram reads nothing."""


def read(run):
    return run.hist_mean_ms("take_handoff_ns")
