"""Mean time from the completer starting a take tick's completion to the
tick's tickets completing (``take_readback_ns``, ``runtime/engine.py``):
the wait for the device result and its copy to the host, per device take
ticket, over the window. With ``take_queue_ms.take`` and the prep, lock
wait, dispatch and hand-off stages it tiles ``take_service_ms.take``. A
node that does not publish the histogram reads nothing."""


def read(run):
    return run.hist_mean_ms("take_readback_ns")
