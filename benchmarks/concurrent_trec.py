"""Paper Fig. 7 analogue: evolving collection with concurrent readers,
writers, and a deleter — MAP tracked live over "years".

Recapitulates the shape of the TREC-4→7 experiment with a synthetic
collection: appender threads ingest per-year document files (one transaction
per file), add term statistics and relevance judgments in *separate*
transactions; query threads run BM25 + PRF and compute AP from judgments
read back out of the index; a deletion thread erases old years so the
collection evolves.  Reports MAP per year and aggregate throughput.
"""

import threading
import time

import numpy as np

from repro.core import (DynamicIndex, Warren, average_precision,
                        collection_stats, expand_query, index_document,
                        ingest_documents, score_bm25)
from repro.data.synth import doc_generator


def scatter_gather_bench(warren, queries, rounds: int = 25,
                         extra_docs: int = 0, smoke: bool = False):
    """Same corpus, same query stream, two servings of a ShardedWarren:

      native/seq    scatter once per group per micro-batch, per-group
                    device top-k, global merge — groups visited in a
                    sequential caller-thread loop
      native/async  the same pipeline with the per-group fan-out on the
                    ScatterGather worker pool

    Prints ms/query + the scatter/score/merge breakdown for each, verifies
    both return ``score_bm25``'s top-10 over the same snapshot (the same
    documents in the same order; scores to float32), and reports the
    native/async speedup over the sequential scatter."""
    from repro.train.serve import BatcherConfig, RetrievalServer

    if extra_docs:                       # give each group real work
        ingest_documents(warren, doc_generator(999, extra_docs), batch=256)
        warren.index.merge_segments()    # serving cost, not merge state
    qs = queries * rounds
    results, times = {}, {}
    for mode in ("native/seq", "native/async"):
        warren.set_async_scatter(mode == "native/async")
        server = RetrievalServer(
            warren, k=10, batcher=BatcherConfig(max_batch=16, max_wait_ms=4))
        for i in (1, 2, 4, 8, 16):               # warm every batch bucket
            server._handle(qs[:i])
        server.timings.reset()
        t0 = time.time()
        handles = [server.batcher.submit(q) for q in qs]
        results[mode] = [h.get(timeout=120) for h in handles]
        times[mode] = time.time() - t0
        print(f"  serving [{mode:>12}]: {1e3 * times[mode] / len(qs):7.2f} "
              f"ms/query wall — {server.timings.summary()}")
        server.close()
    with warren:
        ref = {q: score_bm25(warren, q, k=10) for q in queries}
    same = all(
        [d for d, _ in hits] == [d for d, _ in ref[q]]
        and np.allclose([s for _, s in hits], [s for _, s in ref[q]],
                        rtol=1e-6)
        for res in results.values() for q, hits in zip(qs, res))
    speedup = times["native/seq"] / times["native/async"]
    note = (" (smoke-sized corpus: parity check only, speedup needs the "
            "full run)" if smoke else "")
    print(f"  both paths equal score_bm25: {same}; native/async speedup "
          f"over the sequential scatter: {speedup:.2f}x{note}")
    if not same:
        raise SystemExit("serving paths diverged from score_bm25")
    return speedup


def rebalance_bench(shards: int = 3, replicas: int = 2,
                    smoke: bool = False) -> None:
    """Search latency impact of a LIVE split (and merge) under load.

    Writers keep committing and searchers keep querying while group 0 is
    split in two and the new group is merged back — all through
    ``repro.dist.rebalance.Rebalancer``.  Reports per-phase search latency
    (before / during / after the split), the measured writer stall (the
    routing-table swap window, the only moment writers block), verifies
    ZERO aborted reader transactions, and checks the final state is
    bit-identical to a single index holding exactly the committed docs.
    """
    from repro.dist.rebalance import Rebalancer
    from repro.dist.shard_router import ShardedWarren

    base_docs = 300 if smoke else 2500
    extra_per_writer = 40 if smoke else 250
    n_writers, n_searchers = (2, 2) if smoke else (3, 3)
    queries = ["school education student", "government law state",
               "stock money business", "vibration conductor wind"]

    warren = ShardedWarren(n_shards=shards, replicas=replicas)
    corpus = list(doc_generator(7, base_docs, mean_len=40))
    # small batches: every transaction's appends land on ONE group (hash of
    # the first doc), so fine batching is what spreads mass across groups
    ingest_documents(warren, corpus, batch=8)

    errors: list = []
    committed: list = []
    lat: list = []                       # (timestamp, seconds)
    stop = threading.Event()
    lock = threading.Lock()

    def writer(wid: int) -> None:
        wc = warren.clone()
        for i in range(extra_per_writer):
            docid, text = f"x{wid}-{i}", corpus[(wid * 31 + i) % len(corpus)][1]
            try:
                with wc:
                    wc.transaction()
                    index_document(wc, text, docid=docid)
                    wc.commit()
                with lock:
                    committed.append((docid, text))
            except Exception as e:        # noqa: BLE001 — must not happen
                errors.append(f"writer {docid}: {type(e).__name__}: {e}")
                return

    def searcher(sid: int) -> None:
        wc = warren.clone()
        i = 0
        while not stop.is_set():
            q = queries[(sid + i) % len(queries)]
            i += 1
            try:
                t0 = time.time()
                with wc:
                    score_bm25(wc, q, k=10)
                with lock:
                    lat.append((t0, time.time() - t0))
            except Exception as e:        # noqa: BLE001 — zero reader aborts
                errors.append(f"searcher: {type(e).__name__}: {e}")
                return

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_writers)]
    threads += [threading.Thread(target=searcher, args=(s,))
                for s in range(n_searchers)]
    for t in threads:
        t.start()
    try:
        time.sleep(0.3 if smoke else 1.0)    # a "before" latency window
        # split the busiest group (whole-txn append batches skew the hash)
        def _docs_of(g):
            grp = warren.groups[g]
            idx = grp.replicas[grp.first_alive()]
            return sum(len(s.content.records()) for s in idx._segments)
        source = max(range(warren.n_shards), key=_docs_of)
        rb = Rebalancer(warren)
        split_t0 = time.time()
        new_gid = rb.split_group(source)
        split_t1 = time.time()
        split_stats = rb.last_stats
        time.sleep(0.2 if smoke else 0.5)
        rb.merge_groups(source, new_gid)
        merge_stats = rb.last_stats
        for t in threads[:n_writers]:
            t.join(timeout=300)
        time.sleep(0.2)
    finally:
        stop.set()
    for t in threads[n_writers:]:
        t.join(timeout=30)

    if errors:
        raise SystemExit(f"rebalance bench saw reader/writer failures: "
                         f"{errors[:5]}")

    def pct(xs, p):
        if not xs:
            return float("nan")
        xs = sorted(xs)
        return 1e3 * xs[min(len(xs) - 1, int(p * len(xs)))]

    before = [d for ts, d in lat if ts < split_t0]
    during = [d for ts, d in lat if split_t0 <= ts <= split_t1]
    after = [d for ts, d in lat if ts > split_t1]
    print(f"# live rebalance under load: {shards}x{replicas} groups, "
          f"{len(committed)} concurrent commits, {len(lat)} searches, "
          f"0 aborted reader transactions")
    print(f"  split : {split_stats.summary()}")
    print(f"  merge : {merge_stats.summary()}")
    print(f"  search latency ms (p50/p95): "
          f"before {pct(before, .5):.2f}/{pct(before, .95):.2f}  "
          f"during-split {pct(during, .5):.2f}/{pct(during, .95):.2f} "
          f"({len(during)} queries)  "
          f"after {pct(after, .5):.2f}/{pct(after, .95):.2f}")
    print(f"  writer stall = swap window only: split "
          f"{1e3 * split_stats.swap_s:.2f} ms, merge "
          f"{1e3 * merge_stats.swap_s:.2f} ms")

    # parity: bit-identical to one index over exactly the committed docs
    single = Warren(DynamicIndex())
    ingest_documents(single, corpus, batch=128)
    ingest_documents(single, sorted(committed), batch=1)
    ok = True
    with warren, single:
        n_s = len(warren.annotations(":"))
        n_1 = len(single.annotations(":"))
        ok = ok and n_s == n_1
        for q in queries:
            got = sorted(round(s, 9) for _, s in score_bm25(warren, q, k=10))
            ref = sorted(round(s, 9) for _, s in score_bm25(single, q, k=10))
            ok = ok and got == ref
    print(f"  parity with single-index oracle over {n_s} docs: {ok}")
    if not ok:
        raise SystemExit("rebalanced warren diverged from the oracle")


def run(n_years: int = 3, files_per_year: int = 6, docs_per_file: int = 20,
        n_queries: int = 12, n_writers: int = 4, shards: int = 1,
        replicas: int = 1, async_scatter: bool = False, smoke: bool = False):
    if smoke:
        n_years, files_per_year, docs_per_file = 2, 2, 10
        n_queries, n_writers = 4, 2
    if shards > 1 or replicas > 1:
        from repro.dist.shard_router import ShardedWarren
        warren = ShardedWarren(n_shards=shards, replicas=replicas,
                               async_scatter=async_scatter)
    else:
        warren = Warren(DynamicIndex())
    rng = np.random.default_rng(0)
    queries = {}
    for y in range(n_years):
        for qi in range(n_queries // n_years):
            qid = f"y{y}q{qi}"
            queries[qid] = {"year": y, "text": None, "rel": set()}

    files = []
    for y in range(n_years):
        for f in range(files_per_year):
            docs = list(doc_generator(y * 100 + f, docs_per_file))
            files.append((y, f, docs))

    # assign relevance: each query gets terms from docs of its year
    for qid, q in queries.items():
        y = q["year"]
        _, text = files[y * files_per_year][2][hash(qid) % docs_per_file]
        words = text.split()
        q["text"] = " ".join(words[:4])
        for (fy, _, docs) in files:
            if fy == y:
                for docid, d in docs:
                    if sum(w in d for w in words[:4]) >= 2:
                        q["rel"].add(docid)

    ap_log = []
    log_lock = threading.Lock()
    stop = threading.Event()
    n_txn = [0]

    def appender(files_slice):
        wc = warren.clone()
        for (y, f, docs) in files_slice:
            # txn 1: append the file
            with wc:
                wc.transaction()
                for docid, text in docs:
                    index_document(wc, text, docid=docid)
                    wc.annotate(f"year:{y}", 0, 0)  # marker (see txn 3)
                wc.commit()
            # txn 2: re-read documents, write extra statistics
            with wc:
                wc.transaction()
                roots = wc.annotations(":")
                wc.annotate(f"stats:file:{y}:{f}", int(roots.starts[-1]),
                            int(roots.ends[-1]), float(len(roots)))
                wc.commit()
            # txn 3: relevance annotations
            with wc:
                wc.transaction()
                for docid, text in docs:
                    for qid, q in queries.items():
                        if docid in q["rel"]:
                            lst = wc.annotations("docid:" + docid)
                            if len(lst):
                                wc.annotate("rel:" + qid, int(lst.starts[0]),
                                            int(lst.ends[0]))
                wc.commit()
            n_txn[0] += 3

    def querier(qid):
        wc = warren.clone()
        q = queries[qid]
        while not stop.is_set():
            with wc:
                stats = collection_stats(wc)
                if stats.n_docs < 10:
                    time.sleep(0.01)
                    continue
                weights = expand_query(wc, q["text"], fb_docs=5, fb_terms=6,
                                       stats=stats)
                top = score_bm25(wc, "", k=50, weights=weights, stats=stats)
                # resolve doc addresses -> docids via judgments in the index
                rel_addrs = {int(s) for s in
                             wc.annotations("rel:" + qid).starts}
                ranked_rel = [d for d, _ in top]
                ap = average_precision(ranked_rel, rel_addrs
                                       ) if rel_addrs else 0.0
            with log_lock:
                ap_log.append((time.time(), qid, ap))

    def deleter():
        wc = warren.clone()
        while not stop.is_set():
            time.sleep(0.5)
            with wc:
                docs = wc.annotations(":")
                if len(docs) > (n_years - 1) * files_per_year * docs_per_file:
                    wc.transaction()
                    for i in range(docs_per_file):
                        wc.erase(int(docs.starts[i]), int(docs.ends[i]))
                    wc.commit()
                    n_txn[0] += 1

    t0 = time.time()
    per = max(len(files) // n_writers, 1)
    writers = [threading.Thread(target=appender,
                                args=(files[i * per:(i + 1) * per],))
               for i in range(n_writers)]
    readers = [threading.Thread(target=querier, args=(qid,))
               for qid in queries]
    d = threading.Thread(target=deleter)
    for t in writers + readers + [d]:
        t.start()
    for t in writers:
        t.join()
    time.sleep(0.5)        # let queries see the final state
    stop.set()
    for t in readers + [d]:
        t.join()
    wall = time.time() - t0
    warren.index.merge_segments()

    by_year = {}
    for ts, qid, ap in ap_log:
        y = queries[qid]["year"]
        by_year.setdefault(y, []).append(ap)
    print(f"# {len(files)} files, {n_txn[0]} transactions, "
          f"{len(ap_log)} query executions in {wall:.1f}s "
          f"({len(ap_log) / wall:.0f} q/s) — "
          f"{len(warren.index._segments)} subindexes after merge")
    for y in sorted(by_year):
        aps = by_year[y]
        print(f"  year {y}: final MAP {np.mean(aps[-len(aps)//4 or 1:]):.3f} "
              f"over {len(aps)} runs")
    if shards > 1:
        # sequential vs pooled scatter over the evolved corpus (plus extra
        # synthetic docs so each group does non-trivial per-query work)
        print("# scatter-gather serving (same corpus, fixed query set):")
        scatter_gather_bench(
            warren, [q["text"] for q in queries.values()],
            rounds=2 if smoke else 25,
            extra_docs=200 if smoke else 8000, smoke=smoke)
    if shards > 1:
        warren.close()
    return ap_log


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=1,
                    help="partition the index over N shards (ShardedWarren)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replicas per shard group (quorum commits)")
    ap.add_argument("--async-scatter", action="store_true",
                    help="fan per-group reads out on the ScatterGather "
                         "worker pool (repro.dist.parallel)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny corpus + few rounds: CI-sized sanity run "
                         "that still checks async == sequential results")
    ap.add_argument("--rebalance-mid-run", action="store_true",
                    help="run the live-rebalance benchmark instead: split + "
                         "merge a replica group while writers and searchers "
                         "run, report per-phase search latency, the writer "
                         "stall (swap window), and oracle parity")
    ap.add_argument("--years", type=int, default=3)
    ap.add_argument("--writers", type=int, default=4)
    args = ap.parse_args()
    if args.rebalance_mid_run:
        rebalance_bench(shards=max(args.shards, 2), replicas=args.replicas,
                        smoke=args.smoke)
    else:
        run(n_years=args.years, n_writers=args.writers, shards=args.shards,
            replicas=args.replicas, async_scatter=args.async_scatter,
            smoke=args.smoke)
