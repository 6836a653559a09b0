"""Mean time a device take tick waits for the engine's state lock, from
asking for it to holding it (``take_lock_wait_ns``, ``runtime/engine.py``;
the rx thread holds it across its decode and fold), per ticket, over the
window. A node that does not publish the histogram reads nothing."""


def read(run):
    return run.hist_mean_ms("take_lock_wait_ns")
