"""Traffic: request payloads, arrival times and the loops that drive a
scheduler with them.

One generator reads every traffic file (``traffic/<name>.json``).  Its
keys:

* ``loop``: ``"closed"`` keeps ``queue_batches`` full batches of the
  largest bucket queued, so every step dispatches a full batch;
  ``"open"`` sends requests at their due times whatever the system does.
* ``buckets``, ``max_wait_s``, ``max_queue``: the scheduler's batch
  sizes, batching window and admission limit.
* ``pool``: how many distinct payloads a run draws from the seed.
* open loop: ``rate_per_s``, the mean arrival rate.

An open-loop run of ``seconds`` sends exactly ``round(rate * seconds)``
requests: a Poisson process conditioned on its count, so every seed does
the same amount of work and only the order and spacing change.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np

CLOCK = time.perf_counter


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent streams from one seed of any size."""
    return np.random.default_rng([int(seed) % (2 ** 63), int(stream)])


def arrival_times(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Sorted due times in [0, seconds) for an open-loop traffic."""
    n = int(round(traffic["rate_per_s"] * seconds))
    return np.sort(rng(seed, 1).uniform(0.0, seconds, n))


def payload_order(pool: int, n: int, seed: int) -> np.ndarray:
    """Which pool entry each of ``n`` requests carries."""
    g = rng(seed, 2)
    reps = -(-n // pool)
    return np.concatenate([g.permutation(pool) for _ in range(reps)])[:n]


@dataclasses.dataclass
class Record:
    """Per-request times (seconds on ``CLOCK``) and outcomes of a window."""

    due: np.ndarray
    submit: np.ndarray
    admit: np.ndarray
    done: np.ndarray
    item: np.ndarray          # pool index of each request's payload
    results: List[Optional[np.ndarray]]
    refused: int
    t_start: float
    t_end: float
    stretch: Optional[tuple] = None   # (start, end) of the traced stretch

    @property
    def ok(self) -> np.ndarray:
        return np.array([r is not None for r in self.results], bool)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def _records(tickets, due, item, refused, t0, t1, stretch) -> Record:
    def arr(f):
        return np.array([np.nan if getattr(t, f) is None else getattr(t, f)
                         for t in tickets], float)
    return Record(due=np.asarray(due, float), submit=arr("t_submit"),
                  admit=arr("t_admit"), done=arr("t_done"),
                  item=np.asarray(item), results=[t.result for t in tickets],
                  refused=refused, t_start=t0, t_end=t1, stretch=stretch)


class _Stretch:
    """Calls ``on``/``off`` around the last ``length`` seconds before
    ``end`` (the traced stretch); the host clock marks both ends, after
    ``on`` has returned and before ``off`` is called."""

    def __init__(self, hooks, end: float, length: float):
        self.hooks = hooks
        self.t_on = end - length if hooks else float("inf")
        self.span = None

    def poll(self, now: float) -> None:
        if self.span is None and now >= self.t_on:
            self.hooks[0]()
            self.span = [CLOCK(), None]

    def close(self) -> Optional[tuple]:
        if self.span is None:
            return None
        self.span[1] = CLOCK()
        self.hooks[1]()
        return tuple(self.span)


def drive_closed(sched, pool: np.ndarray, traffic: dict, seed: int,
                 seconds: float, annotate: Callable, stretch_hooks=None,
                 stretch_s: float = 0.0) -> Record:
    """Keep ``queue_batches`` full batches queued; stop topping up at the
    close and serve what is queued, so every batch is full."""
    batch = max(traffic["buckets"])
    depth = traffic.get("queue_batches", 2) * batch
    order = payload_order(len(pool), 1 << 16, seed)
    tickets, due, item = [], [], []
    t0 = CLOCK()
    end = t0 + seconds
    stretch = _Stretch(stretch_hooks, end, stretch_s)
    n = 0
    while True:
        now = CLOCK()
        stretch.poll(now)
        if now < end:
            with annotate("submit"):
                while sched.pending < depth:
                    j = order[n % len(order)]
                    due.append(CLOCK())
                    tickets.append(sched.submit(pool[j]))
                    item.append(j)
                    n += 1
        elif not sched.pending:
            break
        with annotate("step"):
            sched.step()
    t1 = CLOCK()
    span = stretch.close()
    return _records(tickets, due, item, 0, t0, t1, span)


def drive_open(sched, pool: np.ndarray, traffic: dict, seed: int,
               seconds: float, annotate: Callable, stretch_hooks=None,
               stretch_s: float = 0.0) -> Record:
    """Send each request at its due time (or as soon after as the loop
    gets to it); step the scheduler in between; serve what is left."""
    times = arrival_times(traffic, seed, seconds)
    order = payload_order(len(pool), len(times), seed)
    tickets, due, item = [], [], []
    refused = 0
    from_queue_full = _queue_full_type()
    t0 = CLOCK()
    due_abs = t0 + times
    stretch = _Stretch(stretch_hooks, t0 + seconds, stretch_s)
    i, n = 0, len(times)
    max_wait = traffic["max_wait_s"]
    while i < n or sched.pending:
        now = CLOCK()
        stretch.poll(now)
        if i < n and due_abs[i] <= now:
            with annotate("submit"):
                while i < n and due_abs[i] <= now:
                    try:
                        tickets.append(sched.submit(pool[order[i]]))
                        due.append(due_abs[i])
                        item.append(order[i])
                    except from_queue_full:
                        refused += 1
                    i += 1
        if sched.pending:
            with annotate("step"):
                served = sched.step()
            if served:
                continue
        # Nothing to dispatch yet: wait for the next arrival or for the
        # oldest request's batching window, whichever comes first.
        now = CLOCK()
        wake = due_abs[i] if i < n else float("inf")
        if sched.pending:
            wake = min(wake, now + max_wait / 4)
        with annotate("wait"):
            _wait_until(wake, now)
    t1 = CLOCK()
    span = stretch.close()
    return _records(tickets, due, item, refused, t0, t1, span)


def _wait_until(t: float, now: float) -> None:
    if t == float("inf"):
        return
    if t - now > 1e-3:
        time.sleep(t - now - 5e-4)
    while CLOCK() < t:
        pass


def _queue_full_type():
    from repro.runtime.scheduler import QueueFull
    return QueueFull


def quantile(values: np.ndarray, q: float) -> float:
    """Nearest-rank quantile: the smallest value with at least ``q`` of
    the sample at or below it."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        return float("nan")
    k = max(int(np.ceil(q * len(v))) - 1, 0)
    return float(v[k])
