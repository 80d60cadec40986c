"""Images answered in the window over the window's whole length."""


def read(run):
    r = run.record
    return float(r.ok.sum()) / r.seconds
