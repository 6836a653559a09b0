"""Take tickets per packed take row dispatched to the take kernel, over
the window: the ``take_service_ns`` histogram's count over the
``take_rows_dispatched`` counter, so both the folds of open queue
entries and the same-key merges at packing count."""


def read(run):
    tickets, _ = run.hist("take_service_ns")
    rows = run.counter("take_rows_dispatched")
    return tickets / rows if rows > 0 else None
