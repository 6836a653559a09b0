"""Share of the engine feeder's busy time that is its own CPU: the
thread's CPU ns over its wall ns from each tick's drain to the tick's
end (``feeder_cpu_ns`` / ``feeder_busy_ns``, ``runtime/engine.py``),
over the window, in %. Low means the feeder waits (the state lock, the
completion queue, the interpreter lock); high means its own Python. A
node that does not publish the counters reads nothing."""


def read(run):
    busy = run.counter("feeder_busy_ns")
    return 100.0 * run.counter("feeder_cpu_ns") / busy if busy > 0 else None
