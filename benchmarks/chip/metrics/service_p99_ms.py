"""Image server: 99th percentile of dispatch to answer
(``Ticket.t_admit`` to ``Ticket.t_done``), over all answered requests."""
from loadgen import quantile


def read(run):
    r = run.record
    return 1e3 * quantile((r.done - r.admit)[r.ok], 0.99)
