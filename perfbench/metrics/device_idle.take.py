"""Share of the traced window in which no operation ran on the device
(1 - busy / window, busy as the union of device op intervals)."""


def read(run):
    return run.idle_share()
