"""Drive a schedule through the program's entry points and time every
request on the client's clock.

Reads go through a ``send`` of the deployment kind's (its ``submit``,
to the program's ``MicroBatcher``); an open-loop request is timed from
when it was due, so a stalled server also delays the requests queued
behind it.  The batcher serves batches one after another, in arrival
order, so a single waiter thread collects answers in submission order.
Writes go to the kind's writers, which record them in a ``Writes``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

GRACE_S = 60.0      # how long past the window's close answers are awaited


@dataclasses.dataclass
class Reads:
    query: np.ndarray                  # index into the session's pool
    due: np.ndarray
    sent: np.ndarray
    done: np.ndarray                   # nan: never answered
    answers: List[Optional[list]]


@dataclasses.dataclass
class Writes:
    due: List[float]
    commit_start: List[float]
    publish: List[float]               # phase 2 began: visible from here
    ack: List[float]                   # nan: failed
    new: List[Optional[Tuple[int, int]]]   # committed (lo, hi) of the version
    old: List[Tuple[int, int]]             # (lo, hi) it erased
    ranks: List[np.ndarray]


class BatchClock:
    """Times every micro-batch the batcher hands its handler: ``batches``
    holds (start, end, requests).  The batcher serves batches one at a time
    in arrival order, so the k-th batch holds the next ``requests`` reads;
    whatever snapshot a read saw was pinned inside its batch's interval."""

    def __init__(self, batcher):
        self.batches: List[Tuple[float, float, int]] = []
        real = batcher.handler

        def timed(requests):
            t0 = time.perf_counter()
            try:
                return real(requests)
            finally:
                self.batches.append((t0, time.perf_counter(), len(requests)))
        batcher.handler = timed

    def pins(self, reads: "Reads", since: int) -> np.ndarray:
        """(earliest, latest) pin time of each read, from the batches since
        index ``since``; the read's own (sent, done) where they do not add
        up to the reads."""
        out = np.stack([reads.sent, reads.done], axis=1)
        batches = self.batches[since:]
        if sum(n for _, _, n in batches) != len(reads.sent):
            return out
        i = 0
        for t0, t1, n in batches:
            out[i:i + n] = (t0, t1)
            i += n
        return out


def _noop_span(name):
    return contextlib.nullcontext()


def open_loop(send: Callable, schedule, t0: float, write: Callable = None,
              span=_noop_span) -> Tuple[Reads, dict]:
    """Send ``schedule``'s requests at ``t0 + due``, a read ``q`` as
    ``send(q)`` (a handle with ``get``), a write as ``write(at, payload)``;
    return the reads and the generator's lateness."""
    is_read = schedule.query >= 0
    n = int(is_read.sum())
    reads = Reads(schedule.query[is_read].copy(), schedule.due[is_read] + t0,
                  np.full(n, np.nan), np.full(n, np.nan), [None] * n)
    handles: "queue.Queue" = queue.Queue()
    deadline = t0 + float(schedule.due[-1] if len(schedule.due) else 0) \
        + GRACE_S

    def wait_all():
        while True:
            item = handles.get()
            if item is None:
                return
            i, h = item
            try:
                reads.answers[i] = h.get(
                    timeout=max(0.0, deadline - time.perf_counter()))
                reads.done[i] = time.perf_counter()
            except Exception:       # never answered, or failed: done = nan
                pass

    waiter = threading.Thread(target=wait_all, daemon=True)
    waiter.start()
    late = np.zeros(len(schedule.due))
    r = 0
    for j, (due, q) in enumerate(zip(schedule.due, schedule.query)):
        at = t0 + due
        now = time.perf_counter()
        if at > now:
            time.sleep(at - now)
        now = time.perf_counter()
        late[j] = now - at
        if q >= 0:
            with span("bench.submit"):
                h = send(q)
            reads.sent[r] = now
            handles.put((r, h))
            r += 1
        else:
            write(at, schedule.updates[j])
    handles.put(None)
    waiter.join(timeout=GRACE_S + 5)
    return reads, {"late_p50_ms": 1e3 * float(np.median(late)) if len(late)
                   else 0.0,
                   "late_p99_ms": 1e3 * float(np.percentile(late, 99))
                   if len(late) else 0.0,
                   "late_max_ms": 1e3 * float(late.max()) if len(late)
                   else 0.0}


def closed_loop(send: Callable, plan, t0: float, seconds: float
                ) -> Tuple[Reads, dict]:
    """``plan.clients`` callers, each sending its next read (``send(q)``)
    when the last returns, from ``t0`` until ``t0 + seconds``."""
    per_client: Dict[int, list] = {}
    t_end = t0 + seconds

    def client(c: int):
        qs = plan.of(c)
        out = []
        j = 0
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            q = int(qs[j % len(qs)])
            j += 1
            h = send(q)
            try:
                ans = h.get(timeout=max(0.0, t_end + GRACE_S
                                        - time.perf_counter()))
                out.append((q, now, time.perf_counter(), ans))
            except Exception:
                out.append((q, now, np.nan, None))
        per_client[c] = out

    while time.perf_counter() < t0:
        time.sleep(max(0.0, t0 - time.perf_counter()))
    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(plan.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + GRACE_S + 5)
    rows = [row for c in range(plan.clients) for row in per_client.get(c, [])]
    rows.sort(key=lambda row: row[1])
    reads = Reads(np.array([q for q, *_ in rows], np.int64),
                  np.array([s for _, s, _, _ in rows]),
                  np.array([s for _, s, _, _ in rows]),
                  np.array([d for _, _, d, _ in rows]),
                  [a for *_, a in rows])
    return reads, {"late_p50_ms": 0.0, "late_p99_ms": 0.0, "late_max_ms": 0.0}
