"""Chaos: concurrent writers/readers vs. a replica killer (``-m stress``).

N writer threads and M reader threads hammer a ``ShardedWarren(n_shards=3,
replicas=2)`` while commit-path hooks kill one replica per group mid-commit
(both before phase 1's ready — forcing quorum aborts — and between the
phases — forcing single-survivor publishes) and a resurrector thread
streams killed replicas back in.  Invariants:

  * no torn commits: every transaction is fully applied or fully aborted,
    including cross-shard annotate transactions;
  * readers never observe a partial transaction: a document's ``docid:``
    and ``chk:`` annotations (written in the same transaction) appear
    together or not at all;
  * after the dust settles, every replica pair is in address lockstep and
    ``search`` matches a single DynamicIndex rebuilt from exactly the
    committed documents.
"""

import random
import threading
import time

import numpy as np
import pytest

from repro.core import DynamicIndex, Warren, index_document, score_bm25
from repro.dist.shard_router import (QuorumError, ReplicaFailure,
                                     ShardedWarren)

VOCAB = ["school", "education", "student", "government", "law", "state",
         "stock", "money", "business", "vibration", "conductor", "wind"]

N_WRITERS = 3
N_READERS = 2
DOCS_PER_WRITER = 40


def _text(wid: int, i: int) -> str:
    rnd = random.Random(wid * 1000 + i)
    return " ".join(rnd.choice(VOCAB) for _ in range(4 + i % 5))


@pytest.mark.stress
def test_quorum_chaos_no_torn_commits():
    sw = ShardedWarren(n_shards=3, replicas=2)
    hook_lock = threading.Lock()
    counters = {"ready": 0, "mid": 0}

    def kill(group: int, replica: int) -> None:
        # never kill the last live replica: the group would be unrecoverable
        grp = sw.groups[group]
        if sum(grp.alive) >= 2 and grp.alive[replica]:
            grp.mark_failed(replica)

    def on_ready(group: int, replica: int) -> None:
        with hook_lock:
            counters["ready"] += 1
            n = counters["ready"]
        if n % 9 == 3:            # kill BEFORE ready → quorum abort path
            kill(group, replica)

    def mid_commit(warren: ShardedWarren, group: int) -> None:
        with hook_lock:
            counters["mid"] += 1
            n = counters["mid"]
        if n % 7 == 2:            # kill AFTER quorum → survivor publishes
            grp = sw.groups[group]
            kill(group, random.Random(n).choice(grp.live()))

    sw.hooks["on_ready"] = on_ready
    sw.hooks["mid_commit"] = mid_commit

    state_lock = threading.Lock()
    committed = {}                # docid -> text
    aborted = set()
    xtags = {}                    # feature -> expected annotation count
    torn = []                     # hard failures observed by any thread
    stop = threading.Event()

    def writer(wid: int) -> None:
        wc = sw.clone()
        for i in range(DOCS_PER_WRITER):
            docid = f"w{wid}-{i}"
            text = _text(wid, i)
            try:
                with wc:
                    wc.transaction()
                    lo, hi = index_document(wc, text, docid=docid)
                    wc.annotate("chk:" + docid, lo, hi, 1.0)
                    wc.commit()
                with state_lock:
                    committed[docid] = text
            except QuorumError:
                with state_lock:
                    aborted.add(docid)
            except ReplicaFailure:
                with state_lock:
                    aborted.add(docid)
            except RuntimeError as e:   # partial commits must never happen
                torn.append(f"writer {docid}: {e}")
                return
            if i % 6 == 5:              # cross-shard annotate transaction
                feature = f"xt{wid}-{i}:"
                try:
                    with wc:
                        docs = wc.annotations(":")
                        if len(docs) < 6:
                            continue
                        picks = [(int(docs.starts[j]), int(docs.ends[j]))
                                 for j in range(0, len(docs),
                                                max(len(docs) // 3, 1))][:3]
                        wc.transaction()
                        for p, q in picks:
                            wc.annotate(feature, p, q, 1.0)
                        wc.commit()
                    with state_lock:
                        xtags[feature] = len(picks)
                except (QuorumError, ReplicaFailure):
                    with state_lock:
                        xtags[feature] = 0
                except RuntimeError as e:
                    torn.append(f"writer {feature}: {e}")
                    return

    def reader(rid: int) -> None:
        wc = sw.clone()
        rnd = random.Random(rid)
        while not stop.is_set():
            with state_lock:
                sample = rnd.sample(sorted(committed),
                                    min(5, len(committed)))
            if not sample:
                time.sleep(0.005)
                continue
            try:
                with wc:
                    for docid in sample:
                        d = wc.annotations("docid:" + docid)
                        c = wc.annotations("chk:" + docid)
                        if len(d) != len(c):   # same-txn pair must co-appear
                            torn.append(
                                f"reader saw torn doc {docid}: "
                                f"{len(d)} docid vs {len(c)} chk")
                            return
            except ReplicaFailure as e:
                torn.append(f"reader failover exhausted: {e}")
                return

    def resurrector() -> None:
        while not stop.is_set():
            for g, grp in enumerate(sw.groups):
                for r in range(grp.n_replicas):
                    if not grp.alive[r]:
                        try:
                            sw.resurrect(g, r)
                        except ReplicaFailure:
                            pass
            time.sleep(0.002)

    writers = [threading.Thread(target=writer, args=(w,))
               for w in range(N_WRITERS)]
    readers = [threading.Thread(target=reader, args=(r,))
               for r in range(N_READERS)]
    res = threading.Thread(target=resurrector)
    for t in writers + readers + [res]:
        t.start()
    for t in writers:
        t.join(timeout=120)
    stop.set()
    for t in readers + [res]:
        t.join(timeout=30)

    sw.hooks.clear()
    for g, grp in enumerate(sw.groups):      # heal the cluster
        for r in range(grp.n_replicas):
            if not grp.alive[r]:
                sw.resurrect(g, r)

    assert torn == [], torn
    assert counters["ready"] > 0 and counters["mid"] > 0
    assert len(committed) > 20, "chaos killed almost every commit"
    assert aborted, "no quorum aborts were exercised"

    # 1. atomicity: committed docs fully present, aborted docs fully absent
    with sw:
        for docid in committed:
            assert len(sw.annotations("docid:" + docid)) == 1, docid
            assert len(sw.annotations("chk:" + docid)) == 1, docid
        for docid in aborted:
            assert len(sw.annotations("docid:" + docid)) == 0, docid
            assert len(sw.annotations("chk:" + docid)) == 0, docid
        for feature, n in xtags.items():     # cross-shard: all-or-nothing
            assert len(sw.annotations(feature)) in (0, n), feature

    # 2. replica lockstep after resurrection
    for grp in sw.groups:
        a, b = grp.replicas
        assert a._next_addr == b._next_addr
        assert a._next_seq == b._next_seq
        wa, wb = Warren(a), Warren(b)
        with wa, wb:
            for f in (":", "school", "money"):
                fv = sw.featurize(f)
                la, lb = wa.annotations(fv), wb.annotations(fv)
                assert np.array_equal(la.starts, lb.starts)
                assert np.array_equal(la.values, lb.values)

    # 3. equivalence with a single index over exactly the committed docs
    single = Warren(DynamicIndex())
    with single:
        single.transaction()
        for docid in sorted(committed):
            index_document(single, committed[docid], docid=docid)
        single.commit()
    with sw, single:
        for q in ("school education", "money business", "wind state"):
            ref = score_bm25(single, q, k=10)
            got = score_bm25(sw, q, k=10)
            np.testing.assert_allclose(sorted(s for _, s in got),
                                       sorted(s for _, s in ref), rtol=1e-9)


@pytest.mark.stress
def test_chaos_reader_failover_under_rolling_kills():
    """Readers keep answering while every replica is rolled through a
    kill/resurrect cycle; totals only ever grow with commits."""
    sw = ShardedWarren(n_shards=2, replicas=3)
    with sw:
        pass
    stop = threading.Event()
    errors = []
    totals = []

    def reader() -> None:
        # monotonic reads are a SESSION guarantee: each clone must never
        # un-see a commit, but two sessions may run at different snapshots
        wc = sw.clone()
        seen = 0
        while not stop.is_set():
            try:
                with wc:
                    n = len(wc.annotations(":"))
                if n < seen:
                    errors.append(
                        f"doc count went backwards: {n} after {seen}")
                    return
                seen = n
            except ReplicaFailure as e:
                errors.append(str(e))
                return
        totals.append(seen)

    def roller() -> None:
        rnd = random.Random(0)
        while not stop.is_set():
            g = rnd.randrange(sw.n_shards)
            grp = sw.groups[g]
            live = grp.live()
            if len(live) >= 2:
                victim = rnd.choice(live)
                grp.mark_failed(victim)
                time.sleep(0.002)
                sw.resurrect(g, victim)

    threads = [threading.Thread(target=reader) for _ in range(3)]
    threads.append(threading.Thread(target=roller))
    for t in threads:
        t.start()
    wc = sw.clone()
    for i in range(60):
        try:
            with wc:
                wc.transaction()
                index_document(wc, _text(9, i), docid=f"r{i}")
                wc.commit()
        except (QuorumError, ReplicaFailure):
            pass
    time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert errors == [], errors
    assert totals and max(totals) > 0
