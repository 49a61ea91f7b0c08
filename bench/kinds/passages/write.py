"""Updates through the program's commit path: writer threads, each with
its own ``ShardedWarren.clone()``, commit one transaction per update
(erase a passage's current version, append its new one).  Passage ``p``
always goes to writer ``p % writers``, so the updates of one passage
commit in schedule order.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, List

import numpy as np

from drive import Writes, _noop_span


class Versions:
    """Where each passage's current version lives, as the writers see it:
    ``addr[p] = (lo, hi)``."""

    def __init__(self, addrs: np.ndarray):
        self.addr = {p: (int(lo), int(hi)) for p, (lo, hi) in enumerate(addrs)}


class Writer(threading.Thread):
    """Commits updates from its queue, one transaction each.  ``publish_t``
    is when the commit's second phase began (see ``watch_publish``)."""

    def __init__(self, warren, versions: Versions, writes: Writes,
                 lock: threading.Lock, text_of: Callable, span=_noop_span):
        super().__init__(daemon=True)
        self.warren = warren
        self.versions = versions
        self.writes = writes
        self.lock = lock
        self.text_of = text_of
        self.span = span
        self.publish_t = np.nan
        self.q: "queue.Queue" = queue.Queue()
        self.errors: List[BaseException] = []

    def run(self):
        while True:
            item = self.q.get()
            if item is None:
                return
            try:
                self._commit(*item)
            finally:
                self.q.task_done()

    def _commit(self, due, upd):
        from repro.core import ranking
        old = self.versions.addr[upd.passage]
        text = self.text_of(upd.ranks)
        new, commit_start, ack = None, np.nan, np.nan
        self.publish_t = np.nan
        try:
            with self.warren:
                self.warren.transaction()
                self.warren.erase(*old)
                lo, hi = ranking.index_document(self.warren, text)
                commit_start = time.perf_counter()
                with self.span("bench.commit"):
                    remap = self.warren.commit()
                ack = time.perf_counter()
            new = (remap(lo), remap(hi))
            self.versions.addr[upd.passage] = new
        except Exception as e:     # counted as failed; the run goes on
            self.errors.append(e)
        with self.lock:
            w = self.writes
            w.due.append(due)
            w.commit_start.append(commit_start)
            w.publish.append(self.publish_t)
            w.ack.append(ack)
            w.new.append(new)
            w.old.append(old)
            w.ranks.append(upd.ranks)


def watch_publish(warren, writers: List[Writer]) -> None:
    """Time the start of each commit's second phase, when replicas begin to
    publish, through the warren family's ``mid_commit`` hook (called per
    touched group between the two phases, in the committing thread)."""
    by_clone = {id(w.warren): w for w in writers}

    def mid_commit(clone, group):
        w = by_clone.get(id(clone))
        if w is not None and np.isnan(w.publish_t):
            w.publish_t = time.perf_counter()
    warren.hooks["mid_commit"] = mid_commit
