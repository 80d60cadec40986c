"""The readers of the phases that the program's ``predict`` spans carry
(``phases.py``, ``metrics/h2d_wait_ms.py``, ``metrics/fetch_ms.py``): by
hand on made-up spans, and through a traced run on the CPU."""
import os
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
from test_chipbench_runs import OFFLINE, small_bench  # noqa: E402,F401


def _reader(name):
    return harness.load_module(os.path.join(HERE, "metrics", name + ".py"))


def _span(n, h2d, fetch):
    return {"bucket": 32, "n": n, "dispatch_s": 0.001, "device_s": 0.02,
            "h2d_wait_s": h2d, "fetch_s": fetch}


# Three chunks of the window: 32 + 32 + 5 images answered.
SPANS = [_span(32, 0.006, 0.0007), _span(32, 0.007, 0.0006),
         _span(5, 0.005, 0.0005)]


def _run(spans, answered=69):
    ok = np.arange(80) < answered
    return SimpleNamespace(predict_spans=spans, record=SimpleNamespace(ok=ok))


@pytest.mark.parametrize("name,want", [("h2d_wait_ms", 6.0),
                                       ("fetch_ms", 0.6)])
def test_phase_readers_by_hand(name, want):
    assert _reader(name).read(_run(SPANS)) == pytest.approx(want, abs=1e-12)


@pytest.mark.parametrize("name", ["h2d_wait_ms", "fetch_ms"])
def test_phase_readers_need_every_span_of_the_window(name):
    r = _reader(name)
    # A span the tracer dropped: its images are answered, the span is not.
    assert r.read(_run(SPANS[1:])) is None
    # A program whose spans carry no phases (only dispatch and device).
    old = [{"bucket": 32, "dispatch_s": 0.001, "device_s": 0.02}] * 2
    assert r.read(_run(old, answered=64)) is None
    assert r.read(_run([], answered=0)) is None


class _StretchOnly:
    """The profiler's part of a traced run, which only a chip gives,
    stood in by a stretch in which nothing ran on the device."""

    def __init__(self, jax, log_dir):
        self.log_dir = log_dir

    def on(self):
        pass

    def off(self):
        pass

    def reduce(self, record, host):
        return {"busy_s": 0.0, "window_s": 1.0, "ops": [], "host_s": 1.0,
                "images": 0, "breakdown": {"device_ops": [], "idle_gaps": []}}


def test_traced_run_reads_the_predict_phases(small_bench, monkeypatch):  # noqa: F811
    monkeypatch.setattr(harness, "Profile", _StretchOnly)
    monkeypatch.setattr(harness, "TRACE_STRETCH_S", 0.2)
    # The CPU's compiled step holds no Mosaic call to route.
    monkeypatch.setattr(harness.workcount, "route", lambda *a: {})
    r = harness.run(OFFLINE, 2 ** 31 + 98, 0.6, True, time.time(),
                    platform="cpu", bench_path=small_bench)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert {"predict_dispatch_ms", "h2d_wait_ms", "fetch_ms"} <= set(m)
    assert m["h2d_wait_ms"]["value"] >= 0 and m["fetch_ms"]["value"] > 0
