"""Scheduler: share of the window's images whose ``predict`` chunk began
while another ``predict`` of the same server was in progress (the
``overlap`` arg of the program's ``predict`` spans), in %.

No reading where a span lacks ``overlap`` or ``n`` (a program that does
not count overlap), or where the spans' images differ from the images
answered (the tracer dropped a span)."""


def read(run):
    spans = run.predict_spans
    if not spans or any("overlap" not in s or "n" not in s for s in spans):
        return None
    images = sum(s["n"] for s in spans)
    if images != int(run.record.ok.sum()):
        return None
    return 100.0 * sum(s["n"] for s in spans if s["overlap"] >= 1) / images
