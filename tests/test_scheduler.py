"""Continuous-batching scheduler: deterministic fake-clock unit tests.

The schedulers are clock-injectable, so every admission decision
(batching window, coalescing, backpressure) is tested against a fake
clock with zero wall-time dependence; the LM tests additionally prove
the graded runtime property — per-request results are bit-identical to
a dedicated ``Generator`` run and independent of arrival order / batch
composition — on a real packed granite-shape model.
"""
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro import configs
from repro.runtime.scheduler import (GenerateScheduler, ImageScheduler,
                                     QueueFull)
from repro.runtime.serve import Generator, pack_for_serving


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class FakeServer:
    """ImageServer stand-in: identity-ish predict + dispatch recording."""

    def __init__(self, buckets=(4, 8)):
        self.batch_buckets = tuple(buckets)
        self.calls = []

    def predict(self, images):
        self.calls.append(images.shape[0])
        return images.sum(axis=(1, 2, 3), keepdims=True)


class GatedServer(FakeServer):
    """``predict`` of the batch whose first image holds value ``v`` waits
    for ``gates[v]`` (when one is given) and sets ``began[v]`` first;
    the thread each batch ran on is recorded."""

    def __init__(self, firsts, buckets=(4,), gated=True, fail=()):
        super().__init__(buckets)
        self.began = {v: threading.Event() for v in firsts}
        self.gates = {v: threading.Event() for v in firsts} if gated \
            else {}
        self.fail = set(fail)
        self.threads = {}

    def predict(self, images):
        v = float(images[0, 0, 0, 0])
        self.threads[v] = threading.get_ident()
        self.began[v].set()
        if v in self.gates:
            assert self.gates[v].wait(10), f"batch {v} never released"
        if v in self.fail:
            raise RuntimeError(f"batch {v} failed")
        return super().predict(images)


def _img(v, hw=2):
    return np.full((hw, hw, 3), float(v), np.float32)


class TestImageScheduler:
    def test_dispatches_when_largest_bucket_fills(self):
        clk, srv = FakeClock(), FakeServer(buckets=(4, 8))
        s = ImageScheduler(srv, max_wait_s=10.0, clock=clk)
        for i in range(8):
            s.submit(_img(i))
        assert s.step() == 8  # full bucket: no window wait
        assert srv.calls == [8]

    def test_coalesces_within_window_then_flushes_stragglers(self):
        clk, srv = FakeClock(), FakeServer(buckets=(4, 8))
        s = ImageScheduler(srv, max_wait_s=1.0, clock=clk)
        for i in range(3):
            s.submit(_img(i))
        assert s.step() == 0          # below the bucket, inside the window
        assert srv.calls == []
        clk.advance(2.0)
        assert s.step() == 3          # window expired: dispatch the 3
        assert srv.calls == [3]
        assert list(s.dispatched_batches) == [3]

    def test_results_match_and_latency_accounted(self):
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_wait_s=0.5, clock=clk)
        t0 = s.submit(_img(1))
        clk.advance(0.2)
        t1 = s.submit(_img(2))
        clk.advance(1.0)
        s.step()
        np.testing.assert_allclose(t0.result, _img(1).sum(keepdims=True)[:1])
        assert t0.done and t1.done
        # fake clock: submit at 0.0 / 0.2, dispatch+finish at 1.2
        assert t0.queue_wait_s == pytest.approx(1.2)
        assert t1.queue_wait_s == pytest.approx(1.0)
        assert t0.latency_s == pytest.approx(1.2)
        st = s.stats()
        assert st["served"] == 2.0
        assert st["max_latency_s"] == pytest.approx(1.2)

    def test_backpressure_queue_full(self):
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_queue=4, max_wait_s=10.0, clock=clk)
        for i in range(4):
            s.submit(_img(i))
        with pytest.raises(QueueFull):
            s.submit(_img(9))
        assert s.rejected == 1
        s.drain()
        s.submit(_img(9))  # queue drained: accepted again
        assert s.pending == 1

    def test_drain_chunks_by_largest_bucket(self):
        clk, srv = FakeClock(), FakeServer(buckets=(4, 8))
        s = ImageScheduler(srv, max_wait_s=10.0, clock=clk)
        for i in range(11):
            s.submit(_img(i))
        assert s.drain() == 11
        assert srv.calls == [8, 3]

    def test_submit_rejects_mismatched_image_shape(self):
        """A malformed request is rejected at the door — it must never
        strand a whole coalesced batch at dispatch time."""
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_wait_s=0.0, clock=clk)
        with pytest.raises(ValueError, match=r"\(H, W, C\)"):
            s.submit(np.zeros((2, 2), np.float32))  # not an image
        s.submit(_img(1, hw=2))
        with pytest.raises(ValueError, match="does not match"):
            s.submit(_img(2, hw=4))
        assert s.drain() == 1  # the good request still serves

    def test_submit_shape_pinned_by_server_config(self):
        """A server that carries a model config (ImageServer) pins the
        expected shape up front — even the FIRST request is checked."""
        class _Cfg:
            img_size = 4

        class _Api:
            cfg = _Cfg()

        srv = FakeServer()
        srv.api = _Api()
        s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
        with pytest.raises(ValueError, match="does not match"):
            s.submit(_img(0, hw=2))          # wrong even as first request
        s.submit(_img(0, hw=4))
        assert s.drain() == 1

    def test_completed_tickets_drop_payloads(self):
        """Long-running front end: served tickets keep results + stats
        but release their input arrays; history is bounded."""
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_wait_s=0.0, clock=clk, history=4)
        tickets = [s.submit(_img(i)) for i in range(8)]
        s.drain()
        assert all(t.payload is None and t.result is not None
                   for t in tickets)
        assert len(s.served) == 4                  # bounded window
        assert s.stats()["served"] == 8.0          # running aggregate

    def test_arrival_order_independent_results(self):
        clk = FakeClock()
        imgs = [_img(i) for i in range(6)]
        outs = {}
        for order in ([0, 1, 2, 3, 4, 5], [5, 3, 1, 0, 2, 4]):
            s = ImageScheduler(FakeServer(), max_wait_s=0.0, clock=clk)
            tickets = {i: s.submit(imgs[i]) for i in order}
            s.drain()
            outs[tuple(order)] = {i: tickets[i].result for i in order}
        a, b = outs.values()
        for i in range(6):
            np.testing.assert_array_equal(a[i], b[i])


class TestDispatchAhead:
    """A second full largest bucket queued behind the batch being served
    starts on a worker before that batch is handed out."""

    def test_next_batch_starts_before_the_first_is_handed_out(self):
        srv = GatedServer(firsts=(0.0, 4.0, 8.0))
        s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
        tickets = [s.submit(_img(i)) for i in range(12)]  # 3 full buckets
        done = []
        first = threading.Thread(target=lambda: done.append(s.step()))
        first.start()
        try:
            assert srv.began[0.0].wait(10) and srv.began[4.0].wait(10)
            assert not any(t.done for t in tickets)
            assert not srv.began[8.0].is_set()   # at most two in flight
        finally:
            for g in srv.gates.values():
                g.set()
            first.join(10)
        assert not first.is_alive() and done == [4]
        assert [t.done for t in tickets] == [True] * 4 + [False] * 8
        assert s.pending == 8 and s.stats()["pending"] == 8.0
        assert srv.threads[0.0] != threading.get_ident()
        while s.pending:
            s.step()
        assert all(t.done for t in tickets)
        assert s.stats()["dispatched_ahead"] == 2.0

    def test_same_results_as_one_bucket_at_a_time(self):
        imgs = [_img(i) for i in range(14)]    # 4 + 4 + 4 + 2 stragglers

        def serve(ahead):
            srv = FakeServer(buckets=(2, 4))
            s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
            tickets, counts = [], []
            if ahead:                          # every bucket queued at once
                tickets = [s.submit(im) for im in imgs]
                while s.pending:
                    counts.append(s.step())
            else:                              # never a second full bucket
                for k in range(0, len(imgs), 4):
                    tickets += [s.submit(im) for im in imgs[k:k + 4]]
                    counts.append(s.step())
            order = [t.id for t in s.served]
            return (counts, [t.result for t in tickets], order, srv.calls,
                    s.stats()["dispatched_ahead"])

        a, b = serve(True), serve(False)
        assert a[0] == b[0] == [4, 4, 4, 2]
        for x, y in zip(a[1], b[1]):
            np.testing.assert_array_equal(x, y)
        assert a[2] == b[2] == list(range(14))  # FIFO hand-out
        assert a[3] == b[3] == [4, 4, 4, 2]
        assert (a[4], b[4]) == (2.0, 0.0)

    def test_one_batch_queued_runs_inline_without_a_thread(self):
        srv = GatedServer(firsts=(0.0, 8.0), buckets=(4, 8), gated=False)
        s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
        tickets = [s.submit(_img(i)) for i in range(11)]  # 8 + 3
        assert s.step() == 8
        assert s._workers is None and s.pending == 3
        assert s.step() == 3
        assert all(t.done for t in tickets)
        assert set(srv.threads.values()) == {threading.get_ident()}
        assert s.stats()["dispatched_ahead"] == 0.0 and s._workers is None

    def test_pending_counts_in_flight_and_nothing_is_stranded(self):
        srv = FakeServer(buckets=(4,))
        s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
        tickets = []
        for _ in range(5):        # a closed loop: top up to 2 buckets
            while s.pending < 8:
                tickets.append(s.submit(_img(len(tickets))))
            s.step()
            assert s.pending == 4 and len(s._in_flight) == 1
        while s.pending:
            s.step()
        assert len(tickets) == 24 and all(t.done for t in tickets)
        assert s.stats()["served"] == 24.0 and s.stats()["pending"] == 0.0
        assert [t.result.item() for t in tickets] == \
            [12.0 * i for i in range(24)]
        tickets = [s.submit(_img(i)) for i in range(9)]
        assert s.drain() == 9 and all(t.done for t in tickets)

    @pytest.mark.parametrize("bad", [0.0, 4.0])
    def test_worker_error_surfaces_from_the_step_that_waits(self, bad):
        srv = GatedServer(firsts=(0.0, 4.0, 8.0), gated=False, fail=(bad,))
        s = ImageScheduler(srv, max_wait_s=0.0, clock=FakeClock())
        tickets = [s.submit(_img(i)) for i in range(12)]
        if bad == 4.0:
            assert s.step() == 4                # batch 0 is fine
        with pytest.raises(RuntimeError, match=f"batch {bad} failed"):
            s.step()
        assert srv.threads[bad] != threading.get_ident()
        while s.pending:                        # the rest still serve
            s.step()
        k = int(bad)
        assert [t.done for t in tickets] == \
            [i not in range(k, k + 4) for i in range(12)]


@pytest.fixture(scope="module")
def lm():
    api = configs.get("granite-8b", reduced=True)
    params = api.init_params(jax.random.PRNGKey(0), "train")
    packed = pack_for_serving(api, params)
    return Generator(api=api, params=packed)


@pytest.fixture(scope="module")
def prompts(lm):
    rng = np.random.default_rng(7)
    return [rng.integers(0, lm.api.cfg.vocab, (8,)).astype(np.int32)
            for _ in range(5)]


@pytest.fixture(scope="module")
def reference(lm, prompts):
    """Per-request Generator outputs — the bit-equality oracle."""
    return [lm.generate(p.reshape(1, -1), 4)[0] for p in prompts]


class TestGenerateScheduler:
    def test_results_bit_equal_to_generator(self, lm, prompts, reference):
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=2, max_len=32, clock=clk)
        tickets = [s.submit(p, 4) for p in prompts]
        s.run_until_idle()
        for t, want in zip(tickets, reference):
            assert t.done
            np.testing.assert_array_equal(t.result, want)

    def test_arrival_order_independent(self, lm, prompts, reference):
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=3, max_len=32, clock=clk)
        order = [3, 0, 4, 2, 1]
        tickets = {i: s.submit(prompts[i], 4) for i in order}
        s.run_until_idle()
        for i in order:
            np.testing.assert_array_equal(tickets[i].result, reference[i])

    def test_prefill_interleaves_with_inflight_decode(self, lm, prompts,
                                                      reference):
        """A request arriving mid-decode is prefilled while earlier
        slots keep decoding — the continuous-batching property."""
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=4, max_len=32, clock=clk)
        first = s.submit(prompts[0], 6)
        s.step()                 # prefill r0, decode tick 1
        s.step()                 # r0 mid-decode
        assert not first.done
        late = s.submit(prompts[1], 4)
        s.run_until_idle()
        kinds = [(kind, ids) for _, kind, ids in s.events]
        # the late prefill happened strictly between decode ticks of r0
        i_pre = kinds.index(("prefill", (late.id,)))
        decode_before = any(k == "decode" and first.id in ids
                            for k, ids in kinds[:i_pre])
        decode_after = any(k == "decode" and first.id in ids
                           for k, ids in kinds[i_pre:])
        assert decode_before and decode_after
        np.testing.assert_array_equal(late.result, reference[1])

    def test_same_length_prompts_coalesce_one_prefill(self, lm, prompts):
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=4, max_len=32, clock=clk)
        ts = [s.submit(p, 3) for p in prompts[:3]]
        s.step()
        prefills = [ids for _, kind, ids in s.events if kind == "prefill"]
        assert prefills == [tuple(t.id for t in ts)]  # one batched prefill

    def test_admission_window_holds_then_admits(self, lm, prompts):
        """max_wait_s > 0: a below-capacity prompt group waits for the
        batching window, then admits as one prefill (or immediately,
        once enough arrive to fill the admittable group)."""
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=2, max_len=32, max_wait_s=1.0,
                              clock=clk)
        t0 = s.submit(prompts[0], 2)
        s.step()
        assert s.active == 0 and s.pending == 1    # held in the window
        clk.advance(2.0)
        s.step()                                   # window expired
        assert t0.t_admit is not None
        s.run_until_idle()
        assert t0.done

    def test_mixed_prompt_lengths_and_lifetimes(self, lm, prompts):
        """Different prompt lengths never share a prefill/decode group
        but still serve correct, independently-verified results."""
        clk = FakeClock()
        rng = np.random.default_rng(3)
        short = rng.integers(0, lm.api.cfg.vocab, (4,)).astype(np.int32)
        s = GenerateScheduler(lm, slots=4, max_len=32, clock=clk)
        ta = s.submit(prompts[0], 5)
        tb = s.submit(short, 2)
        tc = s.submit(prompts[1], 3)
        s.run_until_idle()
        np.testing.assert_array_equal(
            ta.result, lm.generate(prompts[0].reshape(1, -1), 5)[0])
        np.testing.assert_array_equal(
            tb.result, lm.generate(short.reshape(1, -1), 2)[0])
        np.testing.assert_array_equal(
            tc.result, lm.generate(prompts[1].reshape(1, -1), 3)[0])

    def test_backpressure(self, lm, prompts):
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=1, max_len=32, max_queue=2,
                              clock=clk)
        s.submit(prompts[0], 3)
        s.submit(prompts[1], 3)
        with pytest.raises(QueueFull):
            s.submit(prompts[2], 3)
        assert s.rejected == 1
        s.run_until_idle()
        s.submit(prompts[2], 3)  # accepted after the queue drains

    def test_single_token_job_counted_by_step(self, lm, prompts):
        """n_new=1 finishes at prefill; step()'s completion count and
        run_until_idle's total must include it."""
        s = GenerateScheduler(lm, slots=2, max_len=32, clock=FakeClock())
        t = s.submit(prompts[0], 1)
        assert s.step() == 1
        assert t.done and t.result.shape == (1,)
        ts = [s.submit(p, 1) for p in prompts[:3]]
        assert s.run_until_idle() == 3
        np.testing.assert_array_equal(
            np.stack([x.result for x in ts]).ravel(),
            [lm.generate(p.reshape(1, -1), 1)[0, 0] for p in prompts[:3]])

    def test_rejects_over_length_request(self, lm):
        s = GenerateScheduler(lm, slots=1, max_len=16,
                              clock=FakeClock())
        with pytest.raises(ValueError):
            s.submit(np.ones(10, np.int32), 10)  # 10 + 10 > 16

    def test_latency_accounting_fake_clock(self, lm, prompts):
        clk = FakeClock()
        s = GenerateScheduler(lm, slots=1, max_len=32, clock=clk)
        t0 = s.submit(prompts[0], 2)
        t1 = s.submit(prompts[1], 2)
        clk.advance(1.0)
        s.step()                   # admits + serves r0 (slots=1)
        clk.advance(1.0)
        s.run_until_idle()
        assert t0.queue_wait_s == pytest.approx(1.0)
        assert t1.queue_wait_s == pytest.approx(2.0)  # waited for the slot
        assert t0.done and t1.done


class TestBackpressureDiagnostics:
    """QueueFull is an operator signal, not just an exception: it
    carries queue depth, the oldest waiter's age, and a retry hint."""

    def test_queue_full_attributes(self):
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_queue=2, max_wait_s=0.25, clock=clk)
        s.submit(_img(0))
        clk.advance(1.5)
        s.submit(_img(1))
        with pytest.raises(QueueFull) as ei:
            s.submit(_img(2))
        e = ei.value
        assert e.reason == "queue"
        assert e.depth == 2
        assert e.oldest_wait_s == pytest.approx(1.5)
        assert e.retry_after_s == pytest.approx(0.25)  # the batching window
        assert "2 waiting" in str(e) and "retry" in str(e)

    def test_generate_queue_full_attributes(self, lm, prompts):
        s = GenerateScheduler(lm, slots=1, max_len=32, max_queue=1,
                              clock=FakeClock())
        s.submit(prompts[0], 2)
        with pytest.raises(QueueFull) as ei:
            s.submit(prompts[1], 2)
        assert ei.value.depth == 1 and ei.value.reason == "queue"
        assert ei.value.retry_after_s > 0


class TestNonConvergence:
    """A drive loop that stops making progress must FAIL its pending
    tickets loudly (ids + ages) — never hang, never strand work."""

    def test_drain_failure_reports_ids_and_ages(self):
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_wait_s=10.0, clock=clk)
        tickets = [s.submit(_img(i)) for i in range(3)]
        clk.advance(1.25)
        with pytest.raises(RuntimeError,
                           match="drain did not converge") as ei:
            s.drain(max_steps=0)
        assert "0:1.250s" in str(ei.value)        # id:age diagnostics
        for t in tickets:
            assert t.done and t.outcome == "failed" and t.result is None
            assert "did not converge" in t.note
        assert s.pending == 0                     # queue was cleared
        assert s.failed == 3
        assert any(kind == "drain_abort" for _, kind, _ in s.events)

    def test_run_until_idle_failure_clears_slots(self, lm, prompts):
        s = GenerateScheduler(lm, slots=2, max_len=32, clock=FakeClock())
        t0 = s.submit(prompts[0], 4)
        s.step()                                  # t0 now holds a slot
        t1 = s.submit(prompts[1], 4)
        with pytest.raises(RuntimeError,
                           match="run_until_idle did not converge"):
            s.run_until_idle(max_steps=0)
        assert t0.outcome == "failed" and t1.outcome == "failed"
        assert s.active == 0 and s.pending == 0   # slots + queue cleared
        s.submit(prompts[2], 2)                   # scheduler still usable
        assert s.run_until_idle() == 1


class TestLatencyQuantiles:
    def test_quantiles_from_controlled_latencies(self):
        """Reservoir quantiles with < RESERVOIR_SIZE completions see
        every sample: nearest-rank on the exact latency set."""
        clk, srv = FakeClock(), FakeServer()
        s = ImageScheduler(srv, max_wait_s=0.0, clock=clk)
        for i in range(100):                      # latencies 0.01..1.00
            s.submit(_img(i))
            clk.advance((i + 1) / 100.0)
            s.drain()
            clk.t = float(i + 1) * 10             # reset between requests
        st = s.stats()
        assert st["p50_latency_s"] == pytest.approx(0.51)  # nearest rank
        assert st["p95_latency_s"] == pytest.approx(0.95)
        assert st["p99_latency_s"] == pytest.approx(0.99)
        assert st["max_latency_s"] == pytest.approx(1.00)

    def test_quantiles_zero_when_nothing_served(self):
        s = ImageScheduler(FakeServer(), max_wait_s=0.0, clock=FakeClock())
        st = s.stats()
        assert st["p50_latency_s"] == 0.0 == st["p99_latency_s"]

    def test_slo_counters_present_and_zero_on_plain_schedulers(self):
        """The plain schedulers share the stats contract so dashboards
        need one schema: SLO counters exist and stay zero."""
        clk = FakeClock()
        s = ImageScheduler(FakeServer(), max_wait_s=0.0, clock=clk)
        s.submit(_img(1))
        s.drain()
        st = s.stats()
        for key in ("expired", "degraded", "retried", "failed"):
            assert st[key] == 0.0
