"""Mean time a take ticket waits in the engine's queue, from its
creation to the feeder tick that drains it (``take_queue_ns``,
``runtime/engine.py``; a part of ``take_service_ns``), over the window."""


def read(run):
    return run.hist_mean_ms("take_queue_ns")
