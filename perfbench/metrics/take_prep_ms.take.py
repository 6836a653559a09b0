"""Mean time from the feeder's drain of a device take ticket to the take
dispatch asking for the engine's state lock (``take_prep_ns``,
``runtime/engine.py``): the tick's demotion, GC, re-route, promotions
and merges, then grouping, packing and the ``device_put``, over the
window. A node that does not publish the histogram reads nothing."""


def read(run):
    return run.hist_mean_ms("take_prep_ns")
