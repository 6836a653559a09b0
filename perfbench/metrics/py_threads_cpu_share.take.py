"""CPU used by all of the node's Python threads over the window's length
on the node's own clock: 100 x the difference of ``py_threads_cpu_ns``
(the sum of ``thread_cpu_ns``, each live thread's CPU clock, read with
``/debug/vars``) over that of ``mono_ns``, in % of one core. It can pass
100%: native code that runs on those threads with the interpreter lock
released (the rx walk, XLA's dispatch and copies) is on their clocks
too. A thread that exits inside the window takes its CPU out of the sum.
A node that does not publish the sum reads nothing."""


def read(run):
    close = run.trace_vars0 or run.vars1  # the window's end, as run.counter reads it
    span = run.counter("mono_ns")
    if "py_threads_cpu_ns" not in close or span <= 0:
        return None
    return 100.0 * run.counter("py_threads_cpu_ns") / span
