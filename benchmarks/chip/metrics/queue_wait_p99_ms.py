"""Scheduler: 99th percentile of the wait from a request's due time to
its batch's dispatch (``Ticket.t_admit``), over all answered requests."""
from loadgen import quantile


def read(run):
    r = run.record
    return 1e3 * quantile((r.admit - r.due)[r.ok], 0.99)
