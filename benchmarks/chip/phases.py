"""The phases of the program's ``predict`` spans, as a finished run hands
them to the metric readers (``Run.predict_spans``: the args of each
``predict`` span that started in the window).

A traced ``ImageServer.predict`` puts the length of its phases in its
span's args (``h2d_wait_s``, ``fetch_s``, beside ``dispatch_s`` and
``device_s``) with the chunk's image count ``n``.  A reading needs every
span of the window: where the spans' images fall short of the images
answered (the tracer dropped some), or a span lacks the phase (a program
that does not time it), there is none.
"""
from typing import Optional


def mean_ms(run, key: str) -> Optional[float]:
    """Mean of ``key`` over the window's ``predict`` spans, in ms."""
    spans = run.predict_spans
    if not spans or any(key not in s or "n" not in s for s in spans):
        return None
    if sum(s["n"] for s in spans) != int(run.record.ok.sum()):
        return None
    return 1e3 * sum(s[key] for s in spans) / len(spans)
