"""Telemetry: tracing, metrics, attribution — the observability PR's pins.

Everything here runs against injectable clocks (zero wall-time
dependence) except the bit-neutrality test, which runs a real packed
smoke ResNet twice — traced and untraced — and demands byte-identical
logits.  The contracts pinned:

  * Chrome trace export round-trips, spans nest, timestamps are
    monotone in file order — including under injected clock skew;
  * the disabled path is FREE: ``device_timed`` on the null tracer is
    the identity, ``span`` returns one shared context object;
  * ring-buffer truncation is VISIBLE: dropped events/tickets surface
    in ``stats()`` and the golden drop counters;
  * stats() schema parity: ImageScheduler, GenerateScheduler and
    SLOScheduler expose the IDENTICAL key set (SLO / cache keys zeroed
    where not live);
  * Prometheus exposition parses and carries the golden name set from
    any single instrumented scheduler;
  * chaos runs are traceable: every injected fault appears as a
    ``fault.<kind>`` instant, and tracing never perturbs the seeded
    fault schedule;
  * proportional roofline attribution is conservative: shares sum to
    one, attributed seconds sum to the measurement.
"""
import dataclasses
import json
import sys
import threading

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.precision import PrecisionPolicy
from repro.core.roofline import attribute_measured_time
from repro.runtime.faults import FaultInjector, FaultSpec
from repro.runtime.frontier import FrontierServer, ImageBackend
from repro.runtime.scheduler import GenerateScheduler, ImageScheduler
from repro.runtime.serve import Generator, ImageServer, pack_for_serving
from repro.runtime.slo import HysteresisConfig, SLOScheduler
from repro.runtime.telemetry import (GOLDEN_METRICS, NULL_METRICS,
                                     NULL_TRACER, MetricsRegistry,
                                     NullTracer, Tracer,
                                     as_metrics, as_tracer, declare_golden,
                                     device_time_split, device_timed,
                                     layer_attribution,
                                     parse_prometheus_text,
                                     validate_chrome_trace,
                                     validate_metrics_text)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TickClock:
    """A clock that moves one second at every read."""

    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return float(self.reads)


class NoClock:
    def __call__(self):
        raise AssertionError("clock read")


class FakeServer:
    """ImageServer stand-in (cost-free, sum-pooling predict)."""

    def __init__(self, buckets=(4,)):
        self.batch_buckets = tuple(buckets)
        self.calls = []

    def predict(self, images):
        self.calls.append(images.shape[0])
        return images.sum(axis=(1, 2, 3), keepdims=True)


class CostServer(FakeServer):
    """Predict advances the shared fake clock by ``cost_s``."""

    def __init__(self, clk, cost_s, scale=1.0, buckets=(4,)):
        super().__init__(buckets)
        self.clk = clk
        self.cost_s = cost_s
        self.scale = scale

    def predict(self, images):
        self.clk.advance(self.cost_s)
        return super().predict(images) * self.scale


def _img(v, hw=2):
    return np.full((hw, hw, 3), float(v), np.float32)


def _frontier(clk, costs=(1.0, 0.1)):
    return FrontierServer(
        [(f"p{i}", ImageBackend(CostServer(clk, c, float(i + 1))))
         for i, c in enumerate(costs)])


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_context_and_instants_round_trip(self, tmp_path):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.span("outer", cat="request", tid=7):
            clk.advance(1.0)
            tr.instant("mark", cat="queue", tid=7, args={"n": 3})
            with tr.span("inner", tid=7):
                clk.advance(0.5)
            clk.advance(0.25)
        path = tmp_path / "t.json"
        tr.export(path)
        trace = json.loads(path.read_text())
        assert validate_chrome_trace(trace) == []
        evs = {e["name"]: e for e in trace["traceEvents"]}
        assert evs["process_name"]["ph"] == "M"
        # nesting: inner starts after outer, ends before it (µs units)
        outer, inner = evs["outer"], evs["inner"]
        assert outer["ts"] == 0.0 and outer["dur"] == pytest.approx(1.75e6)
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
        assert evs["mark"]["s"] == "t" and evs["mark"]["args"] == {"n": 3}

    def test_export_is_monotone_even_for_out_of_order_pushes(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        tr.span_at("late", 5.0, 6.0)
        tr.span_at("early", 1.0, 2.0)  # retroactive emission may arrive late
        tr.instant_at("mid", 3.0)
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_ring_buffer_drops_oldest_and_counts(self):
        tr = Tracer(clock=FakeClock(), capacity=4)
        for i in range(10):
            tr.instant_at(f"e{i}", float(i))
        assert len(tr.events) == 4
        assert tr.dropped == 6
        assert [e[1] for e in tr.events] == ["e6", "e7", "e8", "e9"]
        assert tr.chrome_trace()["otherData"]["dropped_events"] == 6

    def test_instant_at_never_reads_the_clock(self):
        class Boom:
            def __call__(self):
                raise AssertionError("clock read")

        tr = Tracer(clock=Boom())
        tr.instant_at("fault.step_error", tr.last_ts, cat="fault")
        tr.span_at("s", 0.0, 1.0)
        assert len(tr.events) == 2
        assert tr.last_ts == 1.0


class TestNullFastPath:
    def test_device_timed_identity(self):
        fn = lambda x: x
        assert device_timed(NULL_TRACER, "predict", fn) is fn

    def test_span_is_one_shared_object(self):
        a = NULL_TRACER.span("x")
        b = NULL_TRACER.span("y", cat="device", tid=3, args={"k": 1})
        assert a is b

    def test_null_records_nothing(self):
        NULL_TRACER.instant("a")
        NULL_TRACER.instant_at("b", 1.0)
        NULL_TRACER.span_at("c", 0.0, 1.0)
        with NULL_TRACER.span("d"):
            pass
        assert len(NULL_TRACER.events) == 0
        assert not NULL_TRACER.enabled

    def test_as_helpers_default_to_shared_nulls(self):
        assert as_tracer(None) is NULL_TRACER
        assert as_metrics(None) is NULL_METRICS
        t = Tracer(clock=FakeClock())
        assert as_tracer(t) is t

    def test_null_metrics_hand_out_shared_noops(self):
        c1 = NULL_METRICS.counter("repro_requests_submitted_total")
        c2 = NULL_METRICS.counter("other")
        assert c1 is c2
        c1.inc(level=3)
        NULL_METRICS.gauge("g").set(5.0)
        NULL_METRICS.histogram("h").observe(0.1)
        assert NULL_METRICS.names() == []
        assert NULL_METRICS.prometheus_text() == ""
        assert declare_golden(NULL_METRICS) is NULL_METRICS


# ---------------------------------------------------------------------------
# Metrics registry + exposition
# ---------------------------------------------------------------------------


class TestMetricsRegistry:
    def test_counter_gauge_histogram_and_exposition(self):
        m = MetricsRegistry()
        m.counter("repro_requests_submitted_total").inc()
        m.counter("repro_requests_submitted_total").inc(2.0, tenant="a")
        m.gauge("repro_queue_depth").set(7)
        h = m.histogram("repro_request_latency_seconds")
        h.observe(0.003)
        h.observe(2.0)
        text = m.prometheus_text()
        parsed = parse_prometheus_text(text)
        assert parsed["repro_requests_submitted_total"]["kind"] == "counter"
        assert m.counter("repro_requests_submitted_total").value() == 1.0
        assert m.counter(
            "repro_requests_submitted_total").value(tenant="a") == 2.0
        assert m.gauge("repro_queue_depth").value() == 7.0
        assert h.count() == 2
        # histogram exposition: cumulative buckets + _sum/_count
        samples = dict(parsed["repro_request_latency_seconds"]["samples"])
        assert samples["repro_request_latency_seconds_count"] == 2
        assert samples["repro_request_latency_seconds_sum"] == \
            pytest.approx(2.003)

    def test_kind_collision_raises(self):
        m = MetricsRegistry()
        m.counter("x")
        with pytest.raises(TypeError):
            m.gauge("x")

    def test_declare_golden_pins_the_dashboard_contract(self):
        m = declare_golden(MetricsRegistry())
        assert set(m.names()) == GOLDEN_METRICS
        assert validate_metrics_text(m.prometheus_text(),
                                     require_golden=True) == []

    def test_validator_flags_missing_golden(self):
        m = MetricsRegistry()
        m.counter("repro_requests_submitted_total").inc()
        problems = validate_metrics_text(m.prometheus_text(),
                                         require_golden=True)
        assert problems and "golden" in problems[0]


# ---------------------------------------------------------------------------
# Scheduler instrumentation
# ---------------------------------------------------------------------------


class TestSchedulerTracing:
    def _run(self, n=6):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        mx = MetricsRegistry()
        srv = CostServer(clk, 0.25)
        s = ImageScheduler(srv, max_wait_s=0.0, clock=clk,
                           tracer=tr, metrics=mx)
        tickets = [s.submit(_img(i)) for i in range(n)]
        while s.pending:
            s.step()
        return clk, tr, mx, s, tickets

    def test_ticket_lifecycle_spans(self):
        clk, tr, mx, s, tickets = self._run()
        names = [e[1] for e in tr.events]
        assert names.count("request") == len(tickets)
        assert names.count("serve") == len(tickets)
        # retroactive spans: request covers submit -> done on the ONE
        # shared fake clock, per-ticket track via tid
        req = [e for e in tr.events if e[1] == "request"]
        for ph, name, cat, tid, ts, dur, args in req:
            assert cat == "request" and dur >= 0.0
            assert args["outcome"] == "ok"
        assert {e[3] for e in req} == {t.id for t in tickets}
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_metrics_reflect_the_run(self):
        clk, tr, mx, s, tickets = self._run(n=6)
        assert mx.counter(
            "repro_requests_submitted_total").value() == 6.0
        assert mx.counter(
            "repro_requests_completed_total").value(outcome="ok") == 6.0
        assert mx.histogram("repro_request_latency_seconds").count() == 6
        assert mx.gauge("repro_queue_depth").value() == 0.0
        assert validate_metrics_text(mx.prometheus_text(),
                                     require_golden=True) == []

    def test_untraced_scheduler_behaves_identically(self):
        def serve(tracer, metrics):
            clk = FakeClock()
            srv = CostServer(clk, 0.25)
            s = ImageScheduler(srv, max_wait_s=0.0, clock=clk,
                               tracer=tracer, metrics=metrics)
            ts = [s.submit(_img(i)) for i in range(5)]
            while s.pending:
                s.step()
            return [np.asarray(t.result) for t in ts], s.stats()

        plain_res, plain_st = serve(None, None)
        traced_res, traced_st = serve(Tracer(clock=FakeClock()),
                                      MetricsRegistry())
        for a, b in zip(plain_res, traced_res):
            np.testing.assert_array_equal(a, b)
        assert plain_st == traced_st

    def test_traced_step_holds_stack_and_tags_each_ticket(self):
        clk, tr, mx, s, tickets = self._run(n=6)   # batches of 4 and 2
        spans = {n: [e for e in tr.events if e[1] == n]
                 for n in ("step", "stack", "complete")}
        assert [e[6] for e in spans["step"]] == [
            {"batch": 0, "n": 4, "ahead": 0}, {"batch": 1, "n": 2, "ahead": 0}]
        for st, sk, cp in zip(spans["step"], spans["stack"],
                              spans["complete"]):
            assert st[2] == sk[2] == cp[2] == "sched"
            # step holds stack, then the server's 0.25 s, then complete
            assert st[4] <= sk[4] and sk[4] + sk[5] <= cp[4]
            assert cp[4] + cp[5] <= st[4] + st[5]
            assert st[5] == pytest.approx(0.25)
        serve = {e[3]: e[6]["batch"] for e in tr.events if e[1] == "serve"}
        assert serve == {t.id: t.batch for t in tickets}
        assert sorted(serve.values()) == [0, 0, 0, 0, 1, 1]

    def test_traced_dispatch_ahead_marks_its_steps(self):
        tr = Tracer(clock=FakeClock())
        s = ImageScheduler(FakeServer(), max_wait_s=0.0, clock=FakeClock(),
                           tracer=tr)
        tickets = [s.submit(_img(i)) for i in range(12)]  # 3 full buckets
        assert [s.step() for _ in range(3)] == [4, 4, 4]
        steps = [e[6] for e in tr.events if e[1] == "step"]
        assert steps == [{"batch": 0, "n": 4, "ahead": 1},
                         {"batch": 1, "n": 4, "ahead": 1},
                         {"batch": 2, "n": 4, "ahead": 0}]
        assert [e[1] for e in tr.events if e[2] == "sched"].count(
            "stack") == 3
        assert [t.batch for t in tickets] == [0] * 4 + [1] * 4 + [2] * 4
        assert s.stats()["dispatched_ahead"] == 2.0

    def test_untraced_step_leaves_no_trace(self):
        null = NullTracer()
        null.clock = NoClock()
        s = ImageScheduler(FakeServer(), max_wait_s=0.0, clock=FakeClock(),
                           tracer=null)
        tickets = [s.submit(_img(i)) for i in range(4)]
        assert s.step() == 4
        assert all(t.done and t.batch is None for t in tickets)
        assert len(null.events) == 0

    def test_dropped_tickets_and_events_are_counted(self):
        clk = FakeClock()
        mx = MetricsRegistry()
        srv = CostServer(clk, 0.1)
        s = ImageScheduler(srv, max_wait_s=0.0, clock=clk, history=4,
                           metrics=mx)
        # the event log floors its bound at 4096: fill it to the brim so
        # the next dispatch's log entry sheds the oldest, visibly
        s.events.extend((0, "prefill", ()) for _ in range(s.events.maxlen))
        for i in range(24):
            s.submit(_img(i))
        while s.pending:
            s.step()
        st = s.stats()
        assert st["served"] == 24.0
        assert st["dropped_tickets"] == 20.0  # history=4 keeps the newest
        assert st["dropped_events"] > 0.0
        assert mx.counter(
            "repro_dropped_tickets_total").value() == st["dropped_tickets"]
        assert mx.counter(
            "repro_dropped_events_total").value() == st["dropped_events"]


class TestStatsSchemaParity:
    """The golden key-set contract: dashboards consume ANY scheduler."""

    GOLDEN_KEYS = {
        "served", "rejected", "pending", "expired", "degraded", "retried",
        "failed", "dispatched_ahead", "mean_latency_s", "max_latency_s", "mean_queue_wait_s",
        "p50_latency_s", "p95_latency_s", "p99_latency_s",
        "dropped_events", "dropped_tickets",
        "level", "throttled", "transitions",
        "cache_bytes_per_slot", "resident_cache_bytes",
        "resident_cache_fp_bytes", "kv_cache_compression",
        "accept_rate", "drafted_tokens", "accepted_tokens",
    }

    def test_image_scheduler_keys(self):
        s = ImageScheduler(FakeServer(), clock=FakeClock())
        assert set(s.stats()) == self.GOLDEN_KEYS

    def test_slo_scheduler_keys(self):
        clk = FakeClock()
        s = SLOScheduler(_frontier(clk), slo_s=10.0,
                         est_serve_s=[1.0, 0.1], clock=clk)
        assert set(s.stats()) == self.GOLDEN_KEYS

    def test_generate_scheduler_keys(self, lm_generator):
        s = GenerateScheduler(lm_generator, slots=2, max_len=32)
        assert set(s.stats()) == self.GOLDEN_KEYS

    def test_slo_zeros_are_live_only_on_slo(self):
        s = ImageScheduler(FakeServer(), clock=FakeClock())
        st = s.stats()
        assert st["level"] == 0.0 and st["throttled"] == 0.0
        assert st["kv_cache_compression"] == 1.0


@pytest.fixture(scope="module")
def lm_generator():
    api = configs.get("granite-8b", reduced=True)
    params = api.init_params(jax.random.PRNGKey(0), "train")
    return Generator(api=api, params=pack_for_serving(api, params))


# ---------------------------------------------------------------------------
# SLO + chaos tracing
# ---------------------------------------------------------------------------


class TestSLOTracing:
    def test_degradation_episode_is_traced(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        mx = MetricsRegistry()
        s = SLOScheduler(_frontier(clk, costs=(1.0, 0.05)), slo_s=2.0,
                         est_serve_s=[1.0, 0.05], clock=clk,
                         hysteresis=HysteresisConfig(up_after=1,
                                                     down_after=2),
                         tracer=tr, metrics=mx)
        for i in range(16):
            s.submit(_img(i))
        while s.pending:
            s.step()
        names = [e[1] for e in tr.events]
        assert "shed" in names  # the degradation-transition instant
        (shed,) = [e for e in tr.events
                   if e[1] == "shed" and e[2] == "slo"][:1]
        assert shed[6]["from"] == 0 and shed[6]["to"] >= 1
        assert shed[6]["point"] == "p1"
        assert mx.counter("repro_frontier_transitions_total").value(
            direction="shed") >= 1.0
        assert mx.counter("repro_frontier_serve_total").value(
            level="1", point="p1") >= 1.0
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_every_injected_fault_appears_in_the_trace(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        mx = MetricsRegistry()
        inj = FaultInjector(
            FaultSpec(step_error_rate=0.4, clock_skew_rate=0.2,
                      clock_skew_s=0.01),
            seed=5).instrument(tracer=tr, metrics=mx)
        skewed = inj.wrap_clock(clk)
        faulty = inj.wrap_frontier(_frontier(clk))
        s = SLOScheduler(faulty, slo_s=50.0, est_serve_s=[1.0, 0.1],
                         clock=skewed, max_retries=5, backoff_s=1e-3,
                         tracer=tr, metrics=mx)
        for i in range(12):
            s.submit(_img(i))
        while s.pending:
            if s.step() == 0:
                clk.advance(1e-3)  # let a retry backoff clear
        fault_events = [e for e in tr.events if e[1].startswith("fault.")]
        assert len(fault_events) == sum(inj.counts.values()) > 0
        by_kind = {}
        for e in fault_events:
            kind = e[1].split(".", 1)[1]
            by_kind[kind] = by_kind.get(kind, 0) + 1
        assert by_kind == dict(inj.counts)
        assert mx.counter("repro_faults_injected_total").value(
            kind="step_error") == inj.counts["step_error"]
        # well-formed even though skew lurched the scheduler's clock
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_tracing_never_perturbs_the_fault_schedule(self):
        def chaos(tracer):
            clk = FakeClock()
            inj = FaultInjector(FaultSpec(step_error_rate=0.5), seed=11) \
                .instrument(tracer=tracer)
            s = SLOScheduler(inj.wrap_frontier(_frontier(clk)), slo_s=50.0,
                             est_serve_s=[1.0, 0.1], clock=clk,
                             max_retries=5, backoff_s=1e-3, tracer=tracer)
            for i in range(10):
                s.submit(_img(i))
            while s.pending:
                if s.step() == 0:
                    clk.advance(1e-3)
            return list(inj.log)

        assert chaos(None) == chaos(Tracer(clock=FakeClock()))


# ---------------------------------------------------------------------------
# Device timing + bit-neutrality on a real packed model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def packed_resnet():
    from repro.models import resnet as R
    api = configs.get("resnet18", reduced=True)
    params = api.init_params(jax.random.PRNGKey(0))
    state = R.init_bn_state(R.specs(api.cfg))
    packed = R.pack_for_serve(api.cfg, params, state, api.policy)
    imgs = np.random.default_rng(0).normal(
        0.4, 0.5, (5, 32, 32, 3)).astype(np.float32)
    return api, packed, imgs


class TestDeviceTiming:
    def test_device_timed_wraps_and_splits(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        mx = MetricsRegistry()
        hist = mx.histogram("repro_device_time_seconds")

        def fn(x):
            clk.advance(0.5)  # "dispatch"
            return x + 1

        timed = device_timed(tr, "decode", fn, metrics_hist=hist)
        assert timed.__wrapped__ is fn
        assert timed(np.float32(1.0)) == 2.0
        split = device_time_split(tr)
        assert split["calls"] == 1
        assert split["dispatch_s"] == pytest.approx(0.5)
        assert split["phases"] == {"decode": pytest.approx(0.5)}
        assert hist.count(phase="decode") == 1

    def test_traced_image_server_is_bit_identical(self, key):
        from repro.models import resnet as R
        api = configs.get("resnet18", reduced=True)
        params = api.init_params(key)
        state = R.init_bn_state(R.specs(api.cfg))
        packed = R.pack_for_serve(api.cfg, params, state, api.policy)
        imgs = np.random.default_rng(0).normal(
            0.4, 0.5, (5, 32, 32, 3)).astype(np.float32)
        plain = ImageServer(api=api, params=packed, batch_buckets=(2, 4))
        tr = Tracer()
        traced = ImageServer(api=api, params=packed, batch_buckets=(2, 4),
                             tracer=tr, metrics=MetricsRegistry())
        a = plain.predict(imgs)
        b = traced.predict(imgs)
        np.testing.assert_array_equal(a, b)  # byte-identical, not close
        split = device_time_split(tr)
        assert split["calls"] == 2  # one bucket-4 + one padded bucket-2
        assert split["device_s"] >= 0.0
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_traced_predict_phases_tile_each_chunk(self, packed_resnet):
        api, packed, imgs = packed_resnet
        plain = ImageServer(api=api, params=packed, batch_buckets=(2, 4))
        tr = Tracer(clock=TickClock())
        traced = ImageServer(api=api, params=packed, batch_buckets=(2, 4),
                             tracer=tr)
        np.testing.assert_array_equal(plain.predict(imgs),
                                      traced.predict(imgs))
        outer = [e for e in tr.events if e[1] == "predict"]
        kids = [e for e in tr.events if e[2] == "host"]
        assert [e[2] for e in outer] == ["device", "device"]
        assert [e[1] for e in kids] == ["put", "launch", "h2d_wait",
                                        "device_wait", "fetch"] * 2
        assert device_time_split(tr)["calls"] == 2
        for o, ch in zip(outer, (kids[:5], kids[5:])):
            # The children follow each other with no gap, from the start
            # of the outer span to its end; every phase is one tick.
            assert [c[4] for c in ch] == [o[4] + i for i in range(5)]
            assert all(c[5] == 1.0 for c in ch) and o[5] == 5.0
            assert o[6]["dispatch_s"] == ch[0][5] + ch[1][5]
            assert o[6]["device_s"] == ch[2][5] + ch[3][5]
            assert o[6]["h2d_wait_s"] == ch[2][5]
            assert o[6]["fetch_s"] == ch[4][5]
            assert ch[0][6] == {"bytes": o[6]["bucket"] * 32 * 32 * 3 * 4}
        assert [o[6]["bucket"] for o in outer] == [4, 2]
        assert [o[6]["n"] for o in outer] == [4, 1]
        assert [o[6]["overlap"] for o in outer] == [0, 0]  # one caller

    def test_untraced_predict_reads_no_clock(self, packed_resnet):
        api, packed, imgs = packed_resnet
        null = NullTracer()
        null.clock = NoClock()
        srv = ImageServer(api=api, params=packed, batch_buckets=(2, 4),
                          tracer=null)
        plain = ImageServer(api=api, params=packed, batch_buckets=(2, 4))
        np.testing.assert_array_equal(srv.predict(imgs), plain.predict(imgs))
        assert len(null.events) == 0

    def test_concurrent_predict_counts_overlap(self, packed_resnet):
        """A chunk whose ``put`` begins while another caller's chunk is
        in progress carries ``overlap`` 1; the values never change."""
        api, packed, imgs = packed_resnet
        tr = Tracer()
        srv = ImageServer(api=api, params=packed, batch_buckets=(2, 4),
                          tracer=tr)
        plain = ImageServer(api=api, params=packed, batch_buckets=(2, 4))
        in_fetch, release = threading.Event(), threading.Event()
        fetch = srv._fetch

        def held_fetch(y, take):
            if not in_fetch.is_set():
                in_fetch.set()
                assert release.wait(30)
            return fetch(y, take)

        srv._fetch = held_fetch
        first = []
        t = threading.Thread(target=lambda: first.append(
            srv.predict(imgs[:4])))
        t.start()
        try:
            assert in_fetch.wait(30)
            second = srv.predict(imgs[4:])
        finally:
            release.set()
            t.join(30)
        assert not t.is_alive() and len(first) == 1
        np.testing.assert_array_equal(
            np.concatenate([first[0], second]), plain.predict(imgs))
        overlap = {e[6]["n"]: e[6]["overlap"] for e in tr.events
                   if e[1] == "predict"}
        assert overlap == {4: 0, 1: 1}
        assert srv._chunks_in_progress == 0

    def test_overlap_count_survives_many_callers(self, packed_resnet):
        """Stress: more callers than cores, a short switch interval; a
        lost update would leave the count off zero or out of range."""
        api, packed, imgs = packed_resnet
        tr = Tracer(capacity=1 << 16)
        srv = ImageServer(api=api, params=packed, batch_buckets=(1,),
                          tracer=tr)
        callers, calls = 16, 3
        errors = []

        def work():
            try:
                for _ in range(calls):
                    srv.predict(imgs[:1])
            except Exception as e:  # reported below, with the others
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=work) for _ in range(callers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads) and errors == []
        overlaps = [e[6]["overlap"] for e in tr.events if e[1] == "predict"]
        assert len(overlaps) == callers * calls
        assert all(0 <= o < callers for o in overlaps)
        assert srv._chunks_in_progress == 0

    def test_traced_generator_is_bit_identical(self, lm_generator):
        api = lm_generator.api
        prompts = np.asarray(
            np.random.default_rng(3).integers(0, api.cfg.vocab, (2, 8)),
            np.int32)
        tr = Tracer()
        traced = Generator(api=api, params=lm_generator.params, tracer=tr)
        a = lm_generator.generate(prompts, 4)
        b = traced.generate(prompts, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        split = device_time_split(tr)
        assert split["phases"].keys() == {"prefill", "decode"}


# ---------------------------------------------------------------------------
# Roofline attribution
# ---------------------------------------------------------------------------


class TestAttribution:
    def _layers(self):
        return [
            {"name": "a", "w_bits": 4, "layer_class": "inner",
             "macs": 1e9, "roofline_s": 1e-3, "compute_s": 1e-3,
             "memory_s": 5e-4, "hbm_bytes": 4e5},
            {"name": "b", "w_bits": 8, "layer_class": "boundary",
             "macs": 2e9, "roofline_s": 3e-3, "compute_s": 1e-3,
             "memory_s": 3e-3, "hbm_bytes": 2.4e6},
        ]

    def test_proportional_attribution_is_conservative(self):
        rep = attribute_measured_time(self._layers(), measured_s=8e-3)
        assert rep["roofline_s"] == pytest.approx(4e-3)
        assert rep["roofline_fraction"] == pytest.approx(0.5)
        shares = [l["share"] for l in rep["layers"]]
        assert sum(shares) == pytest.approx(1.0)
        assert sum(l["attributed_s"] for l in rep["layers"]) == \
            pytest.approx(8e-3)
        a, b = rep["layers"]
        assert a["bound"] == "compute" and b["bound"] == "memory"
        # achieved = 2*macs / attributed: layer a got 1/4 of 8ms
        assert a["achieved_tops"] == pytest.approx(
            2.0 * 1e9 / 2e-3 / 1e12)

    def test_degenerate_inputs_do_not_divide_by_zero(self):
        rep = attribute_measured_time([], measured_s=1.0)
        assert rep["layers"] == [] and rep["roofline_fraction"] == 0.0
        rep = attribute_measured_time(self._layers(), measured_s=0.0)
        assert rep["layers"] == []

    def test_layer_attribution_resolves_policy_and_boundary(self):
        from repro.core.dse import Gemm
        gemms = [Gemm("stem", 64, 147, 16, layer_class="boundary"),
                 Gemm("s1b0c1", 64, 144, 16)]
        pol = PrecisionPolicy(inner_bits=2, k=2)
        rep = layer_attribution(gemms, pol, measured_s=1e-3)
        by = {l["name"]: l for l in rep["layers"]}
        assert by["stem"]["w_bits"] == 8      # boundary pin
        assert by["s1b0c1"]["w_bits"] == 2    # inner policy
        assert rep["measured_s"] == pytest.approx(1e-3)
        assert 0.0 < rep["roofline_fraction"]

    def test_fp_baseline_attributes_at_bf16(self):
        from repro.core.dse import Gemm
        rep = layer_attribution([Gemm("q", 128, 128, 128)],
                                PrecisionPolicy(quantize=False),
                                measured_s=1e-3)
        (layer,) = rep["layers"]
        assert layer["w_bits"] == 16
        assert layer["roofline_tops"] <= 394.0  # cannot exceed int8 peak
