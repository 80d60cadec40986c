"""Image server: mean host time of ``ImageServer.predict`` until its
jitted step returns, host-to-device copy included (the ``dispatch_s`` of
the program's ``predict`` spans)."""


def read(run):
    d = [s["dispatch_s"] for s in run.predict_spans if "dispatch_s" in s]
    return 1e3 * sum(d) / len(d) if d else None
