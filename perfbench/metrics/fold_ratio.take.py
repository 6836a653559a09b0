"""Take tickets per take row queued for the kernel, over the window.

Tickets are the ``take_service_ns`` histogram's count; a ticket that
rode an open queue entry of the same (row, rate, count) is counted in
``take_tickets_folded``, so the rows are tickets less folded tickets.
Same-name tickets that ``_group_tickets`` merges later, at packing, are
not counted by the program, so this is a lower bound of the fold."""


def read(run):
    tickets, _ = run.hist("take_service_ns")
    folded = run.counter("take_tickets_folded")
    rows = tickets - folded
    return tickets / rows if rows > 0 else None
