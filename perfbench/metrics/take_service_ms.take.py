"""Mean service time of a device take ticket, from its submission to
its completion by the feeder and the take fold (``take_service_ns``,
``runtime/engine.py``), over the window."""


def read(run):
    return run.hist_mean_ms("take_service_ns")
