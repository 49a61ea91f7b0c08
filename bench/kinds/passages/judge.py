"""Decide ``correct``: every served answer against the plain reference on
the same snapshot, and in a cell with writes, every acknowledged write
against each replica and each replica's durable log.

Numbers compared, each with its limit (``checks`` in the result line):

- ``unanswered``: reads due in the window that never got an answer
  (failed or past the grace period).  Limit 0.
- ``answers_wrong``: judged answers that are not the reference's top-k:
  a rank's score differs from the reference's at that rank by more than
  the relative tolerance, or a served document scores differently on the
  reference (an id may differ from the reference's only where scores
  tie).  Limit 0.
- ``score_gap``: the widest relative gap between a served score and the
  reference's, at the same rank or for the same document, over the
  judged answers.  Limit: the tolerance.
- ``replica_doc_diff`` (writes): documents where a replica's committed
  state differs from the acknowledged writes: an acknowledged version
  missing, an erased one still there, or one that no write made.  Both
  replicas of every group are read.  Limit 0.
- ``durable_diff`` (writes): the same for what each replica's log
  recovers (``DynamicIndex.recover``), plus commit records missing from
  or extra in each log.  Limit 0.

In a cell with writes a read may see any subset of the writes in flight
while its batch ran (a write is in flight from the start of its commit's
second phase, when replicas begin to publish, until ``commit()``
returns); writes acknowledged before its batch started must be visible.  Batches are timed around the batcher's handler
(``drive.BatchClock``); where they do not add up to the window's reads,
the read's own send and answer times bound it instead.  Each judged read is compared with every such state until one
matches to float32 rounding, else the closest.  The read pins one group
after another, so the erase and the append of one update, which can land
on two groups, count as two halves; within a group, commits publish in
order under the group's write lock, so the read saw a prefix of each
group's halves in flight.  A read with more than ``MAX_STATES`` such
states is counted on stderr, not judged.
"""

from __future__ import annotations

import time

import numpy as np

import generate
from harness import log

from . import reference

RTOL = 1e-5             # float32 sums of float32 impacts
WRITE_SAMPLE = 1000     # reads judged in a cell with writes, drawn by seed
MAX_STATES = 4096       # states a read may have seen, tried in full


def _limit(value, limit) -> dict:
    return {"value": value, "limit": limit}


def run(cell, session, window, seed: int) -> dict:
    """The checks of ``window``, a window driven through ``session``."""
    t0 = time.perf_counter()
    dep, pool, reads, pins = (session.dep, session.pool, window.reads,
                              window.pins)
    corpus, addrs, writes, rtol = dep.corpus, dep.addrs, dep.writes, RTOL
    k = cell.config["server"]["k"]
    bm = cell.config["bm25"]
    n = corpus.n
    docs = [corpus.tokens(i) for i in range(n)] + list(writes.ranks)
    vid_of = {int(lo): i for i, (lo, _) in enumerate(addrs)}
    for j, new in enumerate(writes.new):
        if new is not None:
            vid_of[int(new[0])] = n + j
    post = reference.Postings(docs)
    model = reference.BM25(post, bm["k1"], bm["b"])

    def named(ans):
        return [(vid_of.get(int(a), -1), float(s)) for a, s in ans]

    ok = ~np.isnan(reads.done)
    checks = {"unanswered": _limit(int((~ok).sum()), 0)}
    wrong, gap, unjudged = 0, 0.0, 0
    if not writes.ack:
        live = np.ones(n, bool)
        n_live, sum_dl = n, float(post.dl[:n].sum())
        cache = {}
        for i in np.flatnonzero(ok):
            q = int(reads.query[i])
            key = (q, tuple(map(tuple, reads.answers[i])))
            if key in cache:
                continue
            scores = model.scores(pool[q], live, n_live, sum_dl)
            same, g = reference.compare(named(reads.answers[i]), scores, k,
                                        rtol)
            cache[key] = same
            wrong += not same
            gap = max(gap, g)
        judged = len(cache)
    else:
        wrong, gap, judged, unjudged = _judge_with_writes(
            pool, reads, pins, writes, model, post, n, vid_of, seed, k,
            rtol, named, dep.warren.routing)
        checks.update(_replicas(dep.warren, addrs, writes, dep.log_dir))
    checks["answers_wrong"] = _limit(wrong, 0)
    checks["score_gap"] = _limit(gap, rtol)
    log(f"check: {judged} distinct answers judged"
        + (f", {unjudged} reads with too many writes in flight"
           if unjudged else "")
        + f" in {time.perf_counter() - t0:.3f}s")
    order = ["unanswered", "answers_wrong", "score_gap", "replica_doc_diff",
             "durable_diff"]
    return {name: checks[name] for name in order if name in checks}


def _judge_with_writes(pool, reads, pins, writes, model, post, n, vid_of,
                       seed, k, rtol, named, table):
    ack = np.array(writes.ack, dtype=np.float64)
    publish = np.array(writes.publish, dtype=np.float64)
    old_vid = [vid_of.get(int(lo), -1) for lo, _ in writes.old]
    ok = np.flatnonzero(~np.isnan(reads.done))
    rng = generate.rng_for(seed, 7)
    pick = np.sort(rng.choice(ok, min(len(ok), WRITE_SAMPLE), replace=False))
    pick = pick[np.argsort(pins[pick, 0], kind="stable")]
    by_ack = [j for j in np.argsort(ack, kind="stable") if not np.isnan(ack[j])]
    live = np.zeros(post.n_docs, bool)
    live[:n] = True
    dl = post.dl
    n_live, sum_dl = n, float(dl[:n].sum())
    applied = 0
    wrong, gap, judged, unjudged = 0, 0.0, 0, 0

    def toggle(d, on):
        nonlocal n_live, sum_dl
        if d >= 0 and live[d] != on:
            live[d] = on
            n_live += 1 if on else -1
            sum_dl += dl[d] if on else -dl[d]

    for i in pick:
        lo, hi = pins[i]
        while applied < len(by_ack) and ack[by_ack[applied]] <= lo:
            j = by_ack[applied]
            toggle(old_vid[j], False)
            toggle(n + j, True)
            applied += 1
        flight = sorted((j for j in range(len(ack))
                         if publish[j] < hi and not (ack[j] <= lo)),
                        key=lambda j: publish[j])
        halves: dict = {}
        for j in flight:
            if old_vid[j] >= 0:
                halves.setdefault(table.owner(int(writes.old[j][0])),
                                  []).append((old_vid[j], False))
            if writes.new[j] is not None:
                halves.setdefault(table.owner(int(writes.new[j][0])),
                                  []).append((n + j, True))
        if np.prod([len(h) + 1 for h in halves.values()]) > MAX_STATES:
            unjudged += 1
            continue
        same, g, _ = reference.first_match(
            named(reads.answers[i]), model, pool[int(reads.query[i])], live,
            n_live, sum_dl, list(halves.values()), k, rtol)
        judged += 1
        wrong += not same
        gap = max(gap, g)
    return wrong, gap, judged, unjudged


def _replicas(warren, addrs, writes, log_dir) -> dict:
    """Every replica's committed documents, and what its log recovers,
    against the acknowledged writes."""
    from repro.core.index import DynamicIndex
    from repro.core.log import TransactionLog
    from repro.core.ranking import DOC_FEATURE
    from repro.core.warren import Warren

    acked = [j for j, a in enumerate(writes.ack) if not np.isnan(a)]
    unsure = any(np.isnan(a) for a in writes.ack)
    live = {int(lo) for lo, _ in addrs}
    new_live = set()
    for j in acked:            # writes of one passage are acked in order
        live.discard(int(writes.old[j][0]))
        new_live.discard(int(writes.old[j][0]))
        live.add(int(writes.new[j][0]))
        new_live.add(int(writes.new[j][0]))
    table = warren.routing
    commits = {}
    for j in acked:
        for g in {table.owner(int(writes.old[j][0])),
                  table.owner(int(writes.new[j][0]))}:
            commits[g] = commits.get(g, 0) + 1
    replica_diff, durable_diff = 0, 0
    n_rep = max(g.n_replicas for g in warren.groups)
    for r in range(n_rep):
        seen, logged = set(), set()
        for gid, group in enumerate(warren.groups):
            idx = group.replicas[r]
            w = Warren(idx)
            with w:
                seen |= set(map(int, w.annotations(DOC_FEATURE).starts))
            path = str(log_dir / f"shard{gid:02d}r{r}.log")
            rec = DynamicIndex.recover(path)
            w = Warren(rec)
            with w:
                logged |= set(map(int, w.annotations(DOC_FEATURE).starts))
            n_commits = sum(1 for frame in TransactionLog(path).replay()
                            if frame["t"] == "commit")
            if not unsure:
                durable_diff += abs(n_commits - commits.get(gid, 0))
        replica_diff += len(seen ^ live)
        durable_diff += len(logged ^ new_live)
    return {"replica_doc_diff": _limit(replica_diff, 0),
            "durable_diff": _limit(durable_diff, 0)}
