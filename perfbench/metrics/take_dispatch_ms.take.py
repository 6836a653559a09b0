"""Mean time of the take kernel's jit call with the state lock held
(``take_dispatch_ns``, ``runtime/engine.py``), per device take ticket,
over the window. A node that does not publish the histogram reads
nothing."""


def read(run):
    return run.hist_mean_ms("take_dispatch_ns")
