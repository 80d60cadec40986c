"""The reader of the ``overlap`` arg of the program's ``predict`` spans
(``metrics/predict_overlap_share.py``): by hand on made-up spans, and
through a traced run on the CPU, where the closed loop's two queued
batches keep two ``predict`` calls in progress at once."""
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from test_chipbench_phases import _StretchOnly  # noqa: E402
from test_chipbench_runs import OFFLINE, small_bench  # noqa: E402,F401

READER = harness.load_module(
    os.path.join(HERE, "metrics", "predict_overlap_share.py"))


def _span(n, overlap):
    return {"bucket": 32, "n": n, "dispatch_s": 0.001, "device_s": 0.02,
            "h2d_wait_s": 0.006, "fetch_s": 0.0007, "overlap": overlap}


def _run(spans, answered):
    ok = np.arange(100) < answered
    return SimpleNamespace(predict_spans=spans, record=SimpleNamespace(ok=ok))


@pytest.mark.parametrize("overlaps,want", [([1, 1, 2], 100.0),
                                           ([0, 0, 0], 0.0),
                                           ([0, 1, 1], 100.0 * 37 / 69)])
def test_overlap_share_by_hand(overlaps, want):
    spans = [_span(n, o) for n, o in zip((32, 32, 5), overlaps)]
    assert READER.read(_run(spans, 69)) == pytest.approx(want, abs=1e-12)


def test_overlap_share_needs_every_span_and_the_arg():
    spans = [_span(32, 0), _span(32, 1), _span(5, 1)]
    # A span the tracer dropped: its images are answered, the span is not.
    assert READER.read(_run(spans[1:], 69)) is None
    # A program whose spans carry no ``overlap`` (the parent's).
    old = [{k: v for k, v in s.items() if k != "overlap"} for s in spans]
    assert READER.read(_run(old, 69)) is None
    assert READER.read(_run(old[:1] + spans[1:], 69)) is None
    assert READER.read(_run([], 0)) is None


def test_traced_run_reads_the_overlap(small_bench, monkeypatch):  # noqa: F811
    monkeypatch.setattr(harness, "Profile", _StretchOnly)
    monkeypatch.setattr(harness, "TRACE_STRETCH_S", 0.2)
    # The CPU's compiled step holds no Mosaic call to route.
    monkeypatch.setattr(harness.workcount, "route", lambda *a: {})
    r = harness.run(OFFLINE, 2 ** 31 + 97, 0.6, True, time.time(),
                    platform="cpu", bench_path=small_bench)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    share = r["metrics"]["predict_overlap_share"]
    assert share["unit"] == "%" and 0.0 < share["value"] <= 100.0
