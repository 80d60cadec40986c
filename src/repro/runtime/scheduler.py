"""Continuous-batching serving front end: admission queue -> batched compute.

``runtime/serve.py`` owns the *compute* side of deployment (packed
weights, one jitted graph per batch bucket, optional device-mesh
sharding).  This module owns the *traffic* side: individual requests
arrive one at a time, and a scheduler decides when to coalesce them into
the fixed batch shapes the compiled graphs accept.

Two schedulers, one per family shape:

  * ``ImageScheduler`` (CNN): requests are independent single images.
    The admission queue coalesces them into ``ImageServer``'s batch
    buckets — a batch dispatches as soon as the largest bucket fills, or
    when the oldest request has waited ``max_wait_s`` (classic
    batching-window policy), so latency is bounded while throughput
    comes from full buckets.  When a second full bucket waits behind
    the batch being served, its ``predict`` starts on a worker thread
    before the first is handed out (dispatch-ahead), so the next
    batch's stack and host-to-device copy overlap this one's compute.

  * ``GenerateScheduler`` (LM): requests are (prompt, n_new) generation
    jobs of different lengths and lifetimes.  The scheduler keeps a
    fixed number of decode SLOTS; each ``step()`` first admits waiting
    requests into free slots (prefilling same-length prompts as one
    batched prefill), then advances every in-flight slot by one decode
    token — prefill interleaves with in-flight decode instead of
    waiting for the current batch to finish (continuous batching).
    Slots at the same sequence position share one decode call (the
    decode step's cache write/attention mask take a single scalar
    ``length``), padded up to a decode bucket so the jit cache stays
    bounded.

Both schedulers are DETERMINISTIC and clock-injectable: ``clock`` is any
zero-arg callable returning seconds (tests pass a fake), every request
gets per-phase timestamps (submit / admit / done) on its ``Ticket``, and
``max_queue`` gives backpressure — ``submit`` raises ``QueueFull``
instead of buffering unboundedly.

Per-request results are independent of arrival order and batch
composition: batch entries never mix (every model op is per-example on
the batch axis), and padding duplicates an existing row whose outputs
are discarded — so a request's tokens/logits are bit-identical whether
it was served alone, coalesced, or interleaved mid-decode.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import itertools
import random
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime.serve import _pad_batch
from repro.runtime.telemetry import as_metrics, as_tracer, declare_golden

__all__ = ["QueueFull", "Ticket", "ImageScheduler", "GenerateScheduler"]


class QueueFull(RuntimeError):
    """Backpressure: the admission queue is at ``max_queue`` (or a
    tenant's token bucket is empty); the caller should shed load or
    retry later (HTTP 429 territory).

    Carries enough context for a well-behaved client (or the SLO
    retry/backoff path) to act on the rejection without string parsing:

      * ``depth``:         requests waiting when the submit was refused.
      * ``oldest_wait_s``: how long the head of the queue has waited.
      * ``retry_after_s``: suggested backoff before resubmitting (the
                           serve-time estimate the SLO path uses).
      * ``reason``:        'queue' (admission queue at max_queue) or
                           'tenant' (per-tenant token bucket empty).
    """

    def __init__(self, message: str = "admission queue full", *,
                 depth: int = 0, oldest_wait_s: float = 0.0,
                 retry_after_s: float = 0.0, reason: str = "queue"):
        super().__init__(message)
        self.depth = int(depth)
        self.oldest_wait_s = float(oldest_wait_s)
        self.retry_after_s = float(retry_after_s)
        self.reason = reason


@dataclasses.dataclass
class Ticket:
    """One request's handle: result + per-phase latency accounting.

    SLO fields (``runtime/slo.py``): ``deadline`` is the ABSOLUTE time
    (same clock as the scheduler's) by which the caller needs the
    result, ``tenant`` tags the request for per-tenant admission
    control, and the terminal ``outcome`` is one of

      * ``'ok'``:       served within the deadline (or no deadline).
      * ``'degraded'``: served by a faster/lower-bit plan point.
      * ``'late'``:     served, but past the deadline.
      * ``'expired'``:  cancelled in the queue at deadline (no result).
      * ``'failed'``:   retries exhausted / drive loop aborted (no
                        result; ``note`` says why).

    ``plan_point`` records which frontier plan point actually served
    the request (bit-equality against a dedicated run at that point is
    the graded property), ``retries`` how many transient-failure
    redispatches it survived.
    """

    id: int
    payload: Any = None
    n_new: int = 0                      # LM only: tokens requested
    t_submit: float = 0.0
    t_admit: Optional[float] = None     # first compute dispatch
    t_done: Optional[float] = None
    result: Optional[np.ndarray] = None
    done: bool = False
    deadline: Optional[float] = None    # absolute, scheduler-clock time
    tenant: str = "default"
    outcome: str = ""                   # terminal outcome (see above)
    plan_point: str = ""                # frontier point that served it
    retries: int = 0
    note: str = ""                      # diagnostic detail for failures
    batch: Optional[int] = None         # traced dispatch that served it

    @property
    def queue_wait_s(self) -> Optional[float]:
        return None if self.t_admit is None else self.t_admit - self.t_submit

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def deadline_met(self) -> Optional[bool]:
        """True/False once terminal (None while pending or no deadline).
        Expired/failed tickets never met their deadline."""
        if self.deadline is None or not self.done:
            return None
        return self.result is not None and self.t_done <= self.deadline


class _SchedulerBase:
    """Queue + accounting shared by both front ends.

    A scheduler is a LONG-RUNNING component: latency statistics are
    kept as running aggregates (O(1) memory), the retained
    ticket/event history is bounded by ``history`` (the newest entries,
    for debugging/tests), and a completed ticket drops its input
    payload — callers hold their own ``Ticket`` reference for the
    result.
    """

    RESERVOIR_SIZE = 512  # latency quantile sample (O(1) memory forever)

    def __init__(self, *, max_queue: int, max_wait_s: float,
                 clock: Callable[[], float], history: int = 1024,
                 tracer=None, metrics=None):
        self.max_queue = int(max_queue)
        self.max_wait_s = float(max_wait_s)
        self.clock = clock
        self._queue: Deque[Ticket] = collections.deque()
        self._ids = itertools.count()
        self.rejected = 0
        self.expired = 0     # deadline cancellations (runtime/slo.py)
        self.degraded = 0    # served at a lower-bit frontier point
        self.retried = 0     # transient-failure redispatches
        self.failed = 0      # retries exhausted / drive loop aborted
        self.dispatched_ahead = 0  # batches started while one was in flight
        self.served: Deque[Ticket] = collections.deque(maxlen=history)
        self.events: Deque[Tuple[int, str, Tuple[int, ...]]] = \
            collections.deque(maxlen=max(4 * history, 4096))
        self.dropped_events = 0   # oldest entries the bounded deques shed
        self.dropped_tickets = 0  # (truncation must be visible, not silent)
        self._tick = 0
        self._n_served = 0
        self._lat_sum = self._lat_max = self._qw_sum = 0.0
        # Fixed-size latency reservoir (Vitter's algorithm R, seeded so
        # runs are reproducible): a uniform sample of ALL completions at
        # O(1) memory — safe for a front end that serves forever.
        self._res: List[float] = []
        self._res_seen = 0
        self._res_rng = random.Random(0x510)
        # Telemetry: both default to the shared no-op objects, and every
        # metric handle is cached here so the hot path never does a
        # registry lookup.  The tracer MUST share this scheduler's clock
        # (trace timestamps mix span_at(ticket times) with live reads).
        self.tracer = as_tracer(tracer)
        self.metrics = declare_golden(as_metrics(metrics))
        m = self.metrics
        self._m_submitted = m.counter("repro_requests_submitted_total")
        self._m_rejected = m.counter("repro_requests_rejected_total")
        self._m_completed = m.counter("repro_requests_completed_total")
        self._m_batches = m.counter("repro_batches_total")
        self._m_qdepth = m.gauge("repro_queue_depth")
        self._m_latency = m.histogram("repro_request_latency_seconds")
        self._m_qwait = m.histogram("repro_queue_wait_seconds")
        self._m_drop_ev = m.counter("repro_dropped_events_total")
        self._m_drop_tk = m.counter("repro_dropped_tickets_total")

    def _retry_after_hint(self) -> float:
        """Suggested client backoff on rejection: the batching window is
        the base scheduler's best guess at when a slot frees (the SLO
        scheduler overrides this with its serve-time estimate)."""
        return max(self.max_wait_s, 1e-3)

    def _enqueue(self, ticket: Ticket) -> Ticket:
        if len(self._queue) >= self.max_queue:
            self.rejected += 1
            self._m_rejected.inc(reason="queue")
            now = self.clock()
            oldest = now - self._queue[0].t_submit if self._queue else 0.0
            hint = self._retry_after_hint()
            if self.tracer.enabled:
                self.tracer.instant("reject", cat="queue",
                                    args={"depth": len(self._queue),
                                          "reason": "queue"})
            raise QueueFull(
                f"admission queue full ({len(self._queue)} waiting, "
                f"oldest {oldest:.3f}s); retry in {hint:.3f}s",
                depth=len(self._queue), oldest_wait_s=oldest,
                retry_after_s=hint)
        self._queue.append(ticket)
        self._m_submitted.inc()
        self._m_qdepth.set(len(self._queue))
        if self.tracer.enabled:
            self.tracer.instant("submit", cat="request", tid=ticket.id,
                                args={"tenant": ticket.tenant})
        return ticket

    @property
    def pending(self) -> int:
        return len(self._queue)

    def _log(self, kind: str, tickets: Sequence[Ticket]) -> None:
        if len(self.events) == self.events.maxlen:
            self.dropped_events += 1
            self._m_drop_ev.inc()
        self.events.append((self._tick, kind, tuple(t.id for t in tickets)))
        self._m_batches.inc(phase=kind)
        if self.tracer.enabled:
            self.tracer.instant(kind, cat="sched",
                                args={"tick": self._tick,
                                      "n": len(tickets)})

    def _retire(self, ticket: Ticket) -> None:
        """Append a terminal ticket to the bounded history, counting the
        oldest entry it pushes out."""
        if len(self.served) == self.served.maxlen:
            self.dropped_tickets += 1
            self._m_drop_tk.inc()
        self.served.append(ticket)

    def _trace_terminal(self, ticket: Ticket) -> None:
        """Retroactive lifecycle spans from the timestamps the ticket
        already carries (one call at terminal time — the hot path never
        touches the tracer): an outer ``request`` span enclosing
        ``queue`` (submit -> admit) and ``serve`` (admit -> done), all
        on the ticket's own trace track (tid = ticket id)."""
        tr = self.tracer
        if not tr.enabled:
            return
        tid = ticket.id
        args = {"outcome": ticket.outcome}
        if ticket.plan_point:
            args["plan_point"] = ticket.plan_point
        if ticket.retries:
            args["retries"] = ticket.retries
        if ticket.note:
            args["note"] = ticket.note
        tr.span_at("request", ticket.t_submit, ticket.t_done,
                   cat="request", tid=tid, args=args)
        if ticket.t_admit is not None:
            tr.span_at("queue", ticket.t_submit, ticket.t_admit,
                       cat="request", tid=tid)
            tr.span_at("serve", ticket.t_admit, ticket.t_done,
                       cat="request", tid=tid,
                       args=None if ticket.batch is None
                       else {"batch": ticket.batch})

    def _check_not_terminal(self, ticket: Ticket) -> None:
        """A ticket terminates exactly once — double completion is a
        scheduler bug the chaos suite must be able to catch loudly."""
        if ticket.done:
            raise RuntimeError(
                f"ticket {ticket.id} is already terminal "
                f"({ticket.outcome!r}): double completion")

    def _complete(self, ticket: Ticket) -> None:
        self._check_not_terminal(ticket)
        ticket.t_done = self.clock()
        ticket.done = True
        ticket.payload = None  # the result is what callers keep
        if not ticket.outcome:
            ticket.outcome = "ok"
        if (ticket.deadline is not None and ticket.t_done > ticket.deadline
                and ticket.outcome == "ok"):
            ticket.outcome = "late"  # served, but past the deadline
        self._n_served += 1
        self._lat_sum += ticket.latency_s
        self._lat_max = max(self._lat_max, ticket.latency_s)
        self._qw_sum += ticket.queue_wait_s
        self._sample_latency(ticket.latency_s)
        self._retire(ticket)
        self._m_completed.inc(outcome=ticket.outcome)
        self._m_latency.observe(ticket.latency_s)
        self._m_qwait.observe(ticket.queue_wait_s)
        self._m_qdepth.set(len(self._queue))
        self._trace_terminal(ticket)

    def _expire(self, ticket: Ticket, note: str = "") -> None:
        """Deadline cancellation: terminal without a result, so an
        expired request can never strand a coalesced batch."""
        self._check_not_terminal(ticket)
        ticket.t_done = self.clock()
        ticket.done = True
        ticket.outcome = "expired"
        ticket.note = note
        ticket.payload = None
        self.expired += 1
        self._retire(ticket)
        self._m_completed.inc(outcome="expired")
        self._m_qdepth.set(len(self._queue))
        self._trace_terminal(ticket)

    def _fail(self, ticket: Ticket, note: str = "") -> None:
        """Terminal failure (retries exhausted, aborted drive loop)."""
        self._check_not_terminal(ticket)
        ticket.t_done = self.clock()
        ticket.done = True
        ticket.outcome = "failed"
        ticket.note = note
        ticket.payload = None
        self.failed += 1
        self._retire(ticket)
        self._m_completed.inc(outcome="failed")
        self._m_qdepth.set(len(self._queue))
        self._trace_terminal(ticket)

    # --- non-convergent drive loops ----------------------------------------

    def _pending_tickets(self) -> List[Ticket]:
        """Every ticket the drive loop still owes (queue; subclasses add
        in-flight slots)."""
        return list(self._queue)

    def _fail_pending(self, op: str, max_steps: int) -> RuntimeError:
        """A drive loop that did not converge must not STRAND its
        pending tickets (callers block on ``ticket.done`` forever):
        fail each one with a diagnostic outcome, then report their ids
        and ages so the operator can see what was stuck."""
        now = self.clock()
        pending = self._pending_tickets()
        ages = ", ".join(f"{t.id}:{now - t.t_submit:.3f}s"
                         for t in pending[:16])
        more = "" if len(pending) <= 16 else f" +{len(pending) - 16} more"
        for t in pending:
            self._fail(t, note=f"{op} did not converge")
        self._queue.clear()
        self._log(f"{op}_abort", pending)
        return RuntimeError(
            f"{op} did not converge after {max_steps} steps; failed "
            f"{len(pending)} pending tickets with outcome 'failed' "
            f"(id:age {ages}{more})")

    # --- statistics --------------------------------------------------------

    def _sample_latency(self, lat: float) -> None:
        self._res_seen += 1
        if len(self._res) < self.RESERVOIR_SIZE:
            self._res.append(lat)
        else:
            j = self._res_rng.randrange(self._res_seen)
            if j < self.RESERVOIR_SIZE:
                self._res[j] = lat

    def _quantile(self, sorted_res: List[float], q: float) -> float:
        if not sorted_res:
            return 0.0
        idx = min(int(round(q * (len(sorted_res) - 1))), len(sorted_res) - 1)
        return sorted_res[idx]

    def stats(self) -> Dict[str, float]:
        """Aggregate latency accounting over completed requests.

        Quantiles come from the fixed-size reservoir — a uniform sample
        of every completion so far, not a sliding window.  The key set
        is IDENTICAL across every scheduler (the schema-parity contract
        ``tests/test_telemetry.py`` pins): SLO counters are zero on the
        plain schedulers, cache accounting zero outside the LM front
        end — dashboards consume any scheduler uniformly."""
        n = self._n_served
        res = sorted(self._res)
        return {
            "served": float(n),
            "rejected": float(self.rejected),
            "pending": float(self.pending),
            "expired": float(self.expired),
            "degraded": float(self.degraded),
            "retried": float(self.retried),
            "failed": float(self.failed),
            # dispatch-ahead (live only on ImageScheduler)
            "dispatched_ahead": float(self.dispatched_ahead),
            "mean_latency_s": self._lat_sum / n if n else 0.0,
            "max_latency_s": self._lat_max,
            "mean_queue_wait_s": self._qw_sum / n if n else 0.0,
            "p50_latency_s": self._quantile(res, 0.50),
            "p95_latency_s": self._quantile(res, 0.95),
            "p99_latency_s": self._quantile(res, 0.99),
            # bounded-history truncation (oldest entries shed)
            "dropped_events": float(self.dropped_events),
            "dropped_tickets": float(self.dropped_tickets),
            # SLO machinery (live only on SLOScheduler)
            "level": 0.0,
            "throttled": 0.0,
            "transitions": 0.0,
            # resident KV-cache accounting (live only on GenerateScheduler)
            "cache_bytes_per_slot": 0.0,
            "resident_cache_bytes": 0.0,
            "resident_cache_fp_bytes": 0.0,
            "kv_cache_compression": 1.0,
            # speculative decode (live only on a spec-decoding
            # GenerateScheduler; zero-filled on every other path)
            "accept_rate": 0.0,
            "drafted_tokens": 0.0,
            "accepted_tokens": 0.0,
        }


# ---------------------------------------------------------------------------
# CNN: bucket coalescing
# ---------------------------------------------------------------------------


class ImageScheduler(_SchedulerBase):
    """Admission queue in front of an ``ImageServer``-shaped backend.

    ``server`` needs ``.predict(images) -> logits`` and
    ``.batch_buckets`` (ascending tuple); unit tests inject fakes.

    Admission rule: a batch dispatches when the queue can fill the
    largest bucket, or when the oldest waiting request is older than
    ``max_wait_s`` (then the smallest bucket that fits the stragglers
    is used — the server pads the remainder).  ``step(flush=True)``
    dispatches whatever is queued regardless of the window (drain).

    Dispatch-ahead: while one batch is in flight and a full largest
    bucket is queued behind it, ``step`` stacks that bucket and starts
    its ``predict`` on a worker thread before it waits for the oldest
    batch, so at most two batches are in flight and the next one's
    input path runs while the device computes this one.  Whether it
    engages is read from the queue's depth: with no second full bucket
    queued, ``predict`` runs inline on the caller's thread and no thread
    is made.  Workers run ``predict`` alone; clock reads, logs and
    hand-out stay on the caller's thread, in FIFO order.  ``pending``
    counts queued and in-flight requests.
    """

    def __init__(self, server, *, max_queue: int = 256,
                 max_wait_s: float = 0.005,
                 clock: Callable[[], float] = time.monotonic,
                 history: int = 1024, tracer=None, metrics=None):
        super().__init__(max_queue=max_queue, max_wait_s=max_wait_s,
                         clock=clock, history=history, tracer=tracer,
                         metrics=metrics)
        self.server = server
        self.buckets = tuple(sorted(server.batch_buckets))
        self.dispatched_batches: Deque[int] = collections.deque(
            maxlen=history)
        self._batch_ids = itertools.count()
        # Dispatched batches not yet handed out, oldest first: (tickets,
        # logits or the worker's future); the workers are made at the
        # first dispatch-ahead.
        self._in_flight: Deque[Tuple[List[Ticket], Any]] = \
            collections.deque()
        self._workers: Optional[concurrent.futures.ThreadPoolExecutor] = None
        # Expected request shape: from the server's model config when it
        # carries one (ImageServer), else locked to the first request.
        cfg = getattr(getattr(server, "api", None), "cfg", None)
        self._img_shape = ((cfg.img_size, cfg.img_size, 3)
                           if hasattr(cfg, "img_size") else None)

    def submit(self, image: np.ndarray) -> Ticket:
        """One (H, W, C) image -> a ticket (raises ``QueueFull``).

        Shape-checked here: a malformed request must be rejected at the
        door, not explode a dispatch and strand its whole batch."""
        image = np.asarray(image)
        if self._img_shape is None:
            if image.ndim != 3:
                raise ValueError(
                    f"expected an (H, W, C) image, got shape {image.shape}")
            self._img_shape = image.shape
        elif image.shape != self._img_shape:
            raise ValueError(
                f"image shape {image.shape} does not match this "
                f"scheduler's {self._img_shape}")
        t = Ticket(id=next(self._ids), payload=image,
                   t_submit=self.clock())
        return self._enqueue(t)

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(len(b) for b, _ in self._in_flight)

    def step(self, flush: bool = False) -> int:
        """Complete at most one batch; returns requests completed.  A
        step dispatches at most one batch, two where it starts
        dispatch-ahead with nothing in flight.

        With nothing in flight, the admission rule picks a batch; it runs
        inline unless a full largest bucket is queued behind it.  With
        one batch in flight and a full largest bucket queued, that bucket
        starts on a worker.  Then the oldest batch in flight is waited
        for and handed out; an exception its ``predict`` raised is raised
        here."""
        self._tick += 1
        top = self.buckets[-1]
        t0 = None
        if not self._in_flight:
            if not self._queue:
                return 0
            oldest = self.clock() - self._queue[0].t_submit
            if (len(self._queue) < top and oldest < self.max_wait_s
                    and not flush):
                return 0  # keep coalescing inside the batching window
            take = min(len(self._queue), top)
            t0 = self._dispatch(
                take, on_worker=len(self._queue) >= take + top)
        ahead = len(self._in_flight) == 1 and len(self._queue) >= top
        if ahead:
            self.dispatched_ahead += 1
            t_ahead = self._dispatch(top, on_worker=True)
            t0 = t_ahead if t0 is None else t0
        return self._hand_out_oldest(t0, ahead)

    def _dispatch(self, take: int, on_worker: bool) -> float:
        """Admit the queue's first ``take`` requests, stack them and
        start their ``predict``: on a worker when another batch will be
        dispatched before this one is handed out, else inline.  Returns
        the admission time."""
        batch = [self._queue.popleft() for _ in range(take)]
        now = self.clock()
        for t in batch:
            t.t_admit = now
        self._log("dispatch", batch)
        self.dispatched_batches.append(take)
        tr = self.tracer
        if tr.enabled:
            bid = next(self._batch_ids)
            for t in batch:
                t.batch = bid
            t_stack = tr.clock()
        images = np.stack([t.payload for t in batch])
        if tr.enabled:
            tr.span_at("stack", t_stack, tr.clock(), cat="sched")
        if not on_worker:
            out = self.server.predict(images)
        else:
            if self._workers is None:
                self._workers = concurrent.futures.ThreadPoolExecutor(
                    max_workers=2, thread_name_prefix="image-predict")
            out = self._workers.submit(self.server.predict, images)
        self._in_flight.append((batch, out))
        return now

    def _hand_out_oldest(self, t0: Optional[float], ahead: bool) -> int:
        """Wait for the oldest batch in flight and hand its results out.

        Traced, the step is a ``step`` span (from admission, or from the
        wait when the step admitted nothing; args ``batch``, the
        dispatch counter of the batch handed out, its ``n``, and
        ``ahead``: 1 when the step dispatched ahead) that holds the
        step's ``stack`` spans and ``complete`` (the results handed
        out); the server's own spans lie between.  Each ticket's
        ``serve`` span carries the same ``batch`` id."""
        tr = self.tracer
        if tr.enabled and t0 is None:
            t0 = tr.clock()
        batch, out = self._in_flight.popleft()
        if isinstance(out, concurrent.futures.Future):
            out = out.result()
        if tr.enabled:
            t_complete = tr.clock()
        logits = np.asarray(out)
        for i, t in enumerate(batch):
            t.result = logits[i]
            self._complete(t)
        if tr.enabled:
            t1 = tr.clock()
            tr.span_at("step", t0, t1, cat="sched",
                       args={"batch": batch[0].batch, "n": len(batch),
                             "ahead": int(ahead)})
            tr.span_at("complete", t_complete, t1, cat="sched")
        return len(batch)

    def _pending_tickets(self) -> List[Ticket]:
        return ([t for b, _ in self._in_flight for t in b]
                + list(self._queue))

    def _fail_pending(self, op: str, max_steps: int) -> RuntimeError:
        err = super()._fail_pending(op, max_steps)
        self._in_flight.clear()  # their results are never handed out
        return err

    def drain(self, max_steps: int = 10_000) -> int:
        """Serve until nothing is queued or in flight (flushing partial
        batches).

        If the loop does not converge within ``max_steps``, the pending
        tickets are FAILED (outcome ``'failed'``) rather than stranded,
        and the raised error lists their ids and ages."""
        n = 0
        for _ in range(max_steps):
            if not self.pending:
                return n
            n += self.step(flush=True)
        raise self._fail_pending("drain", max_steps)


# ---------------------------------------------------------------------------
# LM: prefill/decode slot interleaving (continuous batching)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Slot:
    ticket: Ticket
    cache: Any             # per-request cache tree (batch dim kept at 1)
    last_tok: np.ndarray   # (1, 1) int32
    pos: int               # tokens currently in the cache
    remaining: int         # decode steps still owed
    out: List[int]


def _cache_batch_axes(api, max_len: int):
    """Which axis of every decode-cache leaf is the request (batch) axis.

    Probed structurally — ``cache_specs(1, L)`` vs ``cache_specs(2, L)``
    differ in exactly the batch dimension — so slot insert/extract works
    for any family whose cache is a pytree of batched arrays, without
    per-family layout knowledge.
    """
    is_leaf = lambda x: isinstance(x, jax.ShapeDtypeStruct)
    a = api.cache_specs(1, max_len)
    b = api.cache_specs(2, max_len)

    def axis(s1, s2):
        diffs = [i for i, (d1, d2) in enumerate(zip(s1.shape, s2.shape))
                 if d1 != d2]
        if len(diffs) != 1:
            raise ValueError(
                f"cannot locate the batch axis of cache leaf {s1.shape}; "
                f"continuous batching needs a per-request-sliceable cache")
        return diffs[0]

    return jax.tree.map(axis, a, b, is_leaf=is_leaf)


class GenerateScheduler(_SchedulerBase):
    """Continuous-batching front end over a packed LM ``Generator``.

    ``gen`` supplies the jitted prefill/decode and the cache-growing
    logic; this class owns slots, admission and per-request accounting.

    * ``slots``: max requests decoding concurrently.
    * ``max_len``: every slot's cache is allocated at this length, so
      slots are shape-compatible and can share decode calls; a request
      with ``prompt_len + n_new > max_len`` is rejected at submit.
    * ``prefill_buckets`` / ``decode_buckets``: the allowed batch shapes
      (groups are padded up by duplicating a row, so the jit cache holds
      at most ``len(buckets)`` graphs per sequence shape).

    Admission coalesces the FIFO head-run of same-prompt-length requests
    into one batched prefill (held up to ``max_wait_s`` while below the
    admittable group size, like the CNN batching window; the default 0.0
    admits immediately); decode groups in-flight slots by their current
    position (the decode step takes one scalar ``length``) and advances
    each group one token per ``step()``.

    A mesh-sharded ``Generator`` works too: buckets round up to the data
    axis and ``max_len`` to the model axis (the cache's kv_seq split),
    and merged groups re-pin to the generator's cache sharding.
    """

    def __init__(self, gen, *, slots: int = 4, max_len: int = 64,
                 prefill_buckets: Tuple[int, ...] = (1, 2, 4),
                 decode_buckets: Tuple[int, ...] = (1, 2, 4, 8),
                 max_queue: int = 256, max_wait_s: float = 0.0,
                 clock: Callable[[], float] = time.monotonic,
                 history: int = 1024, tracer=None, metrics=None):
        super().__init__(max_queue=max_queue, max_wait_s=max_wait_s,
                         clock=clock, history=history, tracer=tracer,
                         metrics=metrics)
        if gen.api.needs_frames:
            raise NotImplementedError(
                "GenerateScheduler does not carry per-request audio frames")
        self.gen = gen
        # A SpeculativeGenerator carries two packed views of one
        # checkpoint; slots then hold a {"verify","draft"} cache pair and
        # decode advances by spec cycles instead of single steps.
        self._speculative = bool(getattr(gen, "is_speculative", False))
        self.spec_k = int(gen.k) if self._speculative else 0
        self.api = gen.api_verify if self._speculative else gen.api
        self.n_slots = int(slots)
        # A meshed Generator jits with explicit shardings: batch shapes
        # must split evenly over 'data', the cache length over 'model'.
        n_data = n_model = 1
        if gen.mesh is not None:
            n_data = gen.mesh.shape.get("data", 1)
            n_model = gen.mesh.shape.get("model", 1)
        self.max_len = -(-int(max_len) // n_model) * n_model
        rnd = lambda bs: tuple(sorted({-(-b // n_data) * n_data for b in bs}))
        self.prefill_buckets = rnd(prefill_buckets)
        self.decode_buckets = rnd(decode_buckets)
        self._slots: List[Optional[_Slot]] = [None] * self.n_slots
        # The axis probe runs per plan point: a speculative slot's cache
        # is the dict pair and tree.map carries the mirrored structure.
        if self._speculative:
            self._batch_axes = {
                "verify": _cache_batch_axes(gen.api_verify, self.max_len),
                "draft": _cache_batch_axes(gen.api_draft, self.max_len)}
        else:
            self._batch_axes = _cache_batch_axes(self.api, self.max_len)
        # Resident-cache accounting (stats()): bytes of one slot's cache
        # under the serving plan (packed digit planes for kv plans) and
        # under the same plan with the fp16 cache — the quotient is the
        # deployed KV compression, reported live per step.
        from repro.core.plan import strip_kv

        def tree_bytes(specs) -> int:
            return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                       for l in jax.tree.leaves(specs))

        point_apis = ([gen.api_verify, gen.api_draft] if self._speculative
                      else [self.api])
        self.cache_bytes_per_slot = sum(
            tree_bytes(a.cache_specs(1, self.max_len)) for a in point_apis)
        self.cache_fp_bytes_per_slot = sum(
            tree_bytes(dataclasses.replace(a, policy=strip_kv(a.policy))
                       .cache_specs(1, self.max_len)) for a in point_apis)

    # --- slot cache plumbing (family-agnostic via the axis probe) ----------

    def _merge(self, caches: List[Any], pad_to: int):
        """Per-slot cache trees -> one batched tree, padded by repeating
        the last real row (its outputs are discarded)."""
        g = len(caches)
        idx = jnp.asarray(list(range(g)) + [g - 1] * (pad_to - g))

        def leaf(ax, *xs):
            m = xs[0] if g == 1 else jnp.concatenate(xs, axis=ax)
            return jnp.take(m, idx, axis=ax) if pad_to != g else m

        merged = jax.tree.map(leaf, self._batch_axes, *caches)
        if self._speculative:
            sh_v = getattr(self.gen.gen_verify, "_cache_sh", None)
            sh_d = getattr(self.gen.gen_draft, "_cache_sh", None)
            cache_sh = ({"verify": sh_v, "draft": sh_d}
                        if sh_v is not None and sh_d is not None else None)
        else:
            cache_sh = getattr(self.gen, "_cache_sh", None)
        if cache_sh is not None:
            # the meshed decode jit pins its cache in_shardings; slicing/
            # concat left the merged tree on whatever layout jax chose
            merged = jax.device_put(merged, cache_sh)
        return merged

    def _extract(self, cache, i: int):
        """Row ``i`` of a batched cache tree, batch dim kept at size 1."""
        return jax.tree.map(
            lambda ax, x: jax.lax.slice_in_dim(x, i, i + 1, axis=ax),
            self._batch_axes, cache)

    # --- admission ---------------------------------------------------------

    def submit(self, tokens: np.ndarray, n_new: int) -> Ticket:
        """One (L,) or (1, L) prompt -> a ticket (raises ``QueueFull``)."""
        toks = np.asarray(tokens, np.int32).reshape(1, -1)
        if n_new < 1:
            raise ValueError(f"n_new must be >= 1, got {n_new}")
        if toks.shape[1] + n_new > self.max_len:
            raise ValueError(
                f"prompt {toks.shape[1]} + n_new {n_new} exceeds the "
                f"scheduler's max_len {self.max_len}")
        t = Ticket(id=next(self._ids), payload=toks, n_new=int(n_new),
                   t_submit=self.clock())
        return self._enqueue(t)

    @property
    def active(self) -> int:
        return sum(s is not None for s in self._slots)

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _admit(self, flush: bool = False) -> int:
        """Prefill the FIFO head-run of same-length prompts into free
        slots (one batched prefill per head-run), holding below-capacity
        groups inside the ``max_wait_s`` batching window."""
        free = self._free_slots()
        if not free or not self._queue:
            return 0
        plen = self._queue[0].payload.shape[1]
        limit = min(len(free), self.prefill_buckets[-1])
        run = 0
        while (run < len(self._queue) and run < limit
               and self._queue[run].payload.shape[1] == plen):
            run += 1
        oldest = self.clock() - self._queue[0].t_submit
        if run < limit and oldest < self.max_wait_s and not flush:
            return 0  # keep coalescing prompts inside the window
        group: List[Ticket] = []
        while (self._queue and len(group) < limit
               and self._queue[0].payload.shape[1] == plen):
            group.append(self._queue.popleft())
        g = len(group)
        bucket = next(b for b in self.prefill_buckets if b >= g)
        toks = _pad_batch(np.concatenate([t.payload for t in group]), bucket)
        now = self.clock()
        for t in group:
            t.t_admit = now
        self._log("prefill", group)
        if self._speculative:
            # Prefill BOTH packed views of the checkpoint; the first
            # emitted token comes from the verify plan (the shipped one).
            first_tok, pre = self.gen.prefill_slots(jnp.asarray(toks))
            cache = {
                "verify": self.gen.gen_verify._grow_cache(
                    pre["verify"], bucket, plen, self.max_len),
                "draft": self.gen.gen_draft._grow_cache(
                    pre["draft"], bucket, plen, self.max_len)}
            first = np.asarray(first_tok, np.int32)
        else:
            logits, pre_cache = self.gen._prefill(
                self.gen.params, {"tokens": jnp.asarray(toks)})
            cache = self.gen._grow_cache(pre_cache, bucket, plen,
                                         self.max_len)
            first = np.asarray(jnp.argmax(logits, -1), np.int32)
        finished = 0
        for i, t in enumerate(group):
            slot = _Slot(ticket=t, cache=self._extract(cache, i),
                         last_tok=first[i].reshape(1, 1), pos=plen,
                         remaining=t.n_new - 1, out=[int(first[i])])
            if slot.remaining == 0:  # n_new == 1: done at prefill
                self._finish(slot)
                finished += 1
            else:
                self._slots[free.pop(0)] = slot
        return finished

    # --- decode ------------------------------------------------------------

    def _finish(self, slot: _Slot) -> None:
        t = slot.ticket
        t.result = np.asarray(slot.out, np.int32)
        self._complete(t)

    def _spec_tick(self) -> int:
        """Advance every in-flight slot one speculative cycle (up to
        ``spec_k + 1`` tokens); same-position slots share one cycle.

        Acceptance-aware accounting: slot i takes ``min(a_i + 1,
        remaining_i)`` tokens from the verify argmax rows, so slots in
        one group diverge in position and regroup on later ticks.  The
        group's ``k_eff`` is clamped to the smallest remaining budget so
        no slot's cache is written past its submit-time bound."""
        groups: Dict[int, List[int]] = collections.defaultdict(list)
        for i, s in enumerate(self._slots):
            if s is not None:
                groups[s.pos].append(i)
        finished = 0
        for pos in sorted(groups):
            idxs = groups[pos]
            slots = [self._slots[i] for i in idxs]
            g = len(slots)
            bucket = next((b for b in self.decode_buckets if b >= g),
                          self.decode_buckets[-1])
            if g > bucket:
                idxs, slots = idxs[:bucket], slots[:bucket]
                g = bucket
            cache = self._merge([s.cache for s in slots], bucket)
            toks = _pad_batch(np.concatenate([s.last_tok for s in slots]),
                              bucket)
            k_eff = min(self.spec_k, min(s.remaining for s in slots) - 1)
            self._log("decode", [s.ticket for s in slots])
            v_toks, acc, cache = self.gen.spec_cycle(
                cache, jnp.asarray(toks), pos, k_eff, rows=g)
            for i, (slot_i, s) in enumerate(zip(idxs, slots)):
                take = min(int(acc[i]) + 1, s.remaining)
                s.cache = self._extract(cache, i)
                s.out.extend(int(x) for x in v_toks[i, :take])
                s.last_tok = np.asarray(v_toks[i, take - 1],
                                        np.int32).reshape(1, 1)
                s.pos += take
                s.remaining -= take
                if s.remaining == 0:
                    self._finish(s)
                    self._slots[slot_i] = None
                    finished += 1
        return finished

    def _decode_tick(self) -> int:
        """Advance every in-flight slot one token; same-position slots
        share one decode call (scalar ``length``)."""
        if self._speculative:
            return self._spec_tick()
        groups: Dict[int, List[int]] = collections.defaultdict(list)
        for i, s in enumerate(self._slots):
            if s is not None:
                groups[s.pos].append(i)
        finished = 0
        for pos in sorted(groups):
            idxs = groups[pos]
            slots = [self._slots[i] for i in idxs]
            g = len(slots)
            bucket = next((b for b in self.decode_buckets if b >= g),
                          self.decode_buckets[-1])
            if g > bucket:  # more same-position slots than the largest
                idxs, slots = idxs[:bucket], slots[:bucket]  # bucket: rest
                g = bucket                                   # go next step
            cache = self._merge([s.cache for s in slots], bucket)
            toks = _pad_batch(np.concatenate([s.last_tok for s in slots]),
                              bucket)
            self._log("decode", [s.ticket for s in slots])
            logits, cache = self.gen._decode(
                self.gen.params, cache, jnp.asarray(toks),
                jnp.asarray(pos, jnp.int32))
            nxt = np.asarray(jnp.argmax(logits, -1), np.int32)
            for i, (slot_i, s) in enumerate(zip(idxs, slots)):
                s.cache = self._extract(cache, i)
                s.last_tok = nxt[i].reshape(1, 1)
                s.pos += 1
                s.remaining -= 1
                s.out.append(int(nxt[i]))
                if s.remaining == 0:
                    self._finish(s)
                    self._slots[slot_i] = None
                    finished += 1
        return finished

    # --- the drive loop ----------------------------------------------------

    def step(self, flush: bool = False) -> int:
        """One scheduler tick: admit (prefill) then decode one token for
        every in-flight slot.  Returns requests completed this tick
        (including ``n_new == 1`` jobs that finish at prefill)."""
        self._tick += 1
        return self._admit(flush=flush) + self._decode_tick()

    def _pending_tickets(self) -> List[Ticket]:
        return (list(self._queue)
                + [s.ticket for s in self._slots if s is not None])

    def _fail_pending(self, op: str, max_steps: int) -> RuntimeError:
        err = super()._fail_pending(op, max_steps)
        self._slots = [None] * self.n_slots  # in-flight caches released
        return err

    def stats(self) -> Dict[str, float]:
        """Base accounting plus live resident-cache bytes: what the
        in-flight slots hold right now under the serving plan, next to
        what the same occupancy would hold with an fp16 cache."""
        st = super().stats()
        st["cache_bytes_per_slot"] = float(self.cache_bytes_per_slot)
        st["resident_cache_bytes"] = float(
            self.cache_bytes_per_slot * self.active)
        st["resident_cache_fp_bytes"] = float(
            self.cache_fp_bytes_per_slot * self.active)
        st["kv_cache_compression"] = (
            self.cache_fp_bytes_per_slot / self.cache_bytes_per_slot
            if self.cache_bytes_per_slot else 1.0)
        if self._speculative:
            st["accept_rate"] = float(self.gen.accept_rate)
            st["drafted_tokens"] = float(self.gen.drafted_tokens)
            st["accepted_tokens"] = float(self.gen.accepted_tokens)
        return st

    def run_until_idle(self, max_steps: int = 100_000) -> int:
        """Serve until queue and slots are empty (flushing the admission
        window — a drive loop with no new traffic must terminate).

        Non-convergence FAILS the pending tickets (queued AND in-flight
        slots, whose caches are released) instead of stranding them; the
        raised error lists their ids and ages."""
        n = 0
        for _ in range(max_steps):
            if not self._queue and self.active == 0:
                return n
            n += self.step(flush=True)
        raise self._fail_pending("run_until_idle", max_steps)
