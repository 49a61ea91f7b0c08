"""Index-construction throughput (paper §4 build-time discussion):
single-writer vs multi-writer dynamic build, static freeze, and — with
``--tiered`` — hot-tier build rate under background LSM compaction, with
the compaction pause time (the only reader/writer-visible stall) reported
per run so regressions show up per-PR in the CI smoke job.

``--mmap`` is the larger-than-memory serving benchmark: it freezes the
corpus into a v2 block run, then serves BM25 + translate through an
mmap'd :class:`StaticIndex` behind a block cache sized at <= 1/10 of the
run, asserting (in ``--smoke``) bit-identical answers to the resident
dynamic oracle, exact cache byte accounting, and a serving-phase heap
peak below the on-disk corpus size — i.e. the corpus never goes
resident."""

import argparse
import tempfile
import threading
import time

from repro.core import DynamicIndex, Warren, index_document, write_static
from repro.data.synth import doc_generator


def run(n_docs: int = 1500, n_writers: int = 4):
    # single writer
    w = Warren(DynamicIndex())
    docs = list(doc_generator(0, n_docs))
    t0 = time.time()
    with w:
        w.transaction()
        for docid, text in docs:
            index_document(w, text, docid=docid)
        w.commit()
    single_s = time.time() - t0

    # multi writer (one txn per chunk per thread)
    w2 = Warren(DynamicIndex())
    per = n_docs // n_writers
    t0 = time.time()

    def worker(tid):
        wc = w2.clone()
        chunk = docs[tid * per:(tid + 1) * per]
        for i in range(0, len(chunk), 64):
            with wc:
                wc.transaction()
                for docid, text in chunk[i:i + 64]:
                    index_document(wc, text, docid=docid)
                wc.commit()

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    multi_s = time.time() - t0
    w2.index.merge_segments()

    with tempfile.TemporaryDirectory() as td:
        t0 = time.time()
        write_static(w.index, td + "/s")
        static_s = time.time() - t0

    tok = sum(len(t.split()) for _, t in docs)
    print(f"# {n_docs} docs, ~{tok} words")
    print(f"single-writer dynamic: {single_s:6.2f}s "
          f"({n_docs / single_s:7.0f} docs/s)")
    print(f"{n_writers}-writer dynamic:     {multi_s:6.2f}s "
          f"({n_docs / multi_s:7.0f} docs/s)")
    print(f"static freeze:         {static_s:6.2f}s")
    _gauge_build(n_docs, single_s, multi_s, static_s)
    return {"single_s": single_s, "multi_s": multi_s, "static_s": static_s}


def _gauge_build(n_docs, single_s, multi_s, static_s=None) -> None:
    from repro import obs

    reg = obs.registry()
    reg.gauge("build_docs_per_s", "dynamic build throughput",
              mode="single").set(n_docs / single_s)
    if multi_s is not None:
        reg.gauge("build_docs_per_s", mode="multi").set(n_docs / multi_s)
    if static_s is not None:
        reg.gauge("build_static_freeze_s",
                  "wall time to freeze the build into a static run"
                  ).set(static_s)


def run_tiered(n_docs: int = 1500, batch: int = 64,
               freeze_segments: int = 4, max_runs: int = 3,
               smoke: bool = False):
    """Hot-tier build rate with the background compactor freezing and
    merging concurrently; reports run counts and compaction pause times."""
    from repro.core import score_bm25
    from repro.tiered import Compactor, TieredStore

    docs = list(doc_generator(0, n_docs))
    with tempfile.TemporaryDirectory() as td:
        store = TieredStore(td + "/tiered", auto_merge_threshold=8)
        compactor = Compactor(store, freeze_segments=freeze_segments,
                              max_runs=max_runs, interval_s=0.01).start()
        w = store.warren()
        t0 = time.time()
        for i in range(0, len(docs), batch):
            with w:
                w.transaction()
                for docid, text in docs[i:i + batch]:
                    index_document(w, text, docid=docid)
                w.commit()
        build_s = time.time() - t0
        compactor.stop(drain=True)
        m = store.metrics
        with w:
            n_indexed = len(w.annotations(":"))
            top = score_bm25(w, "school education student", k=10)
        ok = n_indexed == n_docs
        print(f"# tiered build: {n_docs} docs, batch {batch}")
        print(f"hot-tier build:        {build_s:6.2f}s "
              f"({n_docs / build_s:7.0f} docs/s)")
        print(f"compaction:            {m.summary()}")
        print(f"state:                 {store.n_runs} runs, "
              f"{len(store.hot._segments)} hot segments, "
              f"manifest v{store.manifest.version}")
        print(f"post-compaction reads: {n_indexed}/{n_docs} docs visible, "
              f"top-10 len {len(top)} -> {'OK' if ok else 'MISMATCH'}")
        store.close()
        if smoke and not ok:
            raise SystemExit("tiered smoke: indexed-doc count mismatch")
        if smoke and m.n_freezes == 0:
            raise SystemExit("tiered smoke: compactor never froze the "
                             "hot tier")
        _gauge_build(n_docs, build_s, None)
        return {"build_s": build_s, "n_freezes": m.n_freezes,
                "n_merges": m.n_merges, "total_pause_s": m.total_pause_s,
                "max_pause_s": m.max_pause_s}


def run_mmap(n_docs: int = 1500, rounds: int = 3, smoke: bool = False):
    """Freeze ``n_docs`` into one v2 block run, then serve it through an
    mmap'd StaticIndex whose block cache holds <= 1/10 of the run bytes.
    Returns serving percentiles + cache stats; ``smoke`` turns the
    invariants (parity, accounting, ratio, bounded heap) into hard
    failures for CI."""
    import gc
    import tracemalloc

    import numpy as np

    from repro.core import score_bm25
    from repro.core.runfile import DEFAULT_BLOCK_SIZE
    from repro.core.static import LazyContentStore, StaticIndex, run_bytes
    from repro.tiered.cache import BlockCache

    queries = ["school education student", "government law state",
               "money business company", "water room house"]
    docs = list(doc_generator(0, n_docs))
    with tempfile.TemporaryDirectory() as td:
        w = Warren(DynamicIndex())
        t0 = time.time()
        with w:
            w.transaction()
            for docid, text in docs:
                index_document(w, text, docid=docid)
            w.commit()
        build_s = time.time() - t0
        d = td + "/run"
        write_static(w.index, d)
        corpus_bytes = run_bytes(d)

        # reference answers from the RESIDENT dynamic oracle (the repo's
        # invariant: static layout is bit-identical to the dynamic index
        # holding the same committed transactions)
        with w:
            ref_scores = {q: score_bm25(w, q, k=10) for q in queries}
            sample = [f"docid:doc0_{i}" for i in range(0, n_docs,
                                                       max(1, n_docs // 37))]
            ref_texts = {}
            for f in sample:
                lst = w.annotations(f)
                ref_texts[f] = w.translate(int(lst.starts[0]),
                                           int(lst.ends[0]))
        del w
        gc.collect()

        capacity = max(8 * DEFAULT_BLOCK_SIZE, corpus_bytes // 16)
        ratio = corpus_bytes / capacity
        cache = BlockCache(capacity_bytes=capacity)

        tracemalloc.start()
        si = StaticIndex(d, block_cache=cache)
        assert isinstance(si.content, LazyContentStore)
        lat = []
        parity_ok = True
        for _ in range(rounds):
            for q in queries:
                t0 = time.time()
                got = score_bm25(si, q, k=10)
                lat.append(time.time() - t0)
                ref = ref_scores[q]
                if [g for g, _ in got] != [r for r, _ in ref] or \
                        not np.allclose([s for _, s in got],
                                        [s for _, s in ref], rtol=1e-12):
                    parity_ok = False
            for f, want in ref_texts.items():
                lst = si.annotations(f)
                if si.translate(int(lst.starts[0]),
                                int(lst.ends[0])) != want:
                    parity_ok = False
        _, heap_peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()

        stats = cache.stats()
        cache.check_accounting()
        si.close()
        lat.sort()
        p95 = lat[int(0.95 * (len(lat) - 1))]

        reg = None
        from repro import obs
        reg = obs.registry()
        reg.gauge("mmap_serve_p95_ms",
                  "p95 query latency serving a v2 run via mmap + block "
                  "cache").set(1e3 * p95)
        reg.gauge("mmap_corpus_over_cache",
                  "on-disk run bytes over block-cache capacity (>=10 "
                  "proves larger-than-memory serving)").set(ratio)

        print(f"# mmap serve: {n_docs} docs, run {corpus_bytes} B, "
              f"cache {capacity} B ({ratio:.1f}x)")
        print(f"dynamic build:         {build_s:6.2f}s "
              f"({n_docs / build_s:7.0f} docs/s)")
        print(f"serve p95:             {1e3 * p95:6.2f} ms over "
              f"{len(lat)} queries")
        print(f"cache:                 {stats['hits']} hits / "
              f"{stats['misses']} misses / {stats['evictions']} evictions, "
              f"{stats['bytes']}/{capacity} B resident")
        print(f"serving heap peak:     {heap_peak} B "
              f"({'OK' if heap_peak < corpus_bytes else 'UNBOUNDED'} vs "
              f"corpus {corpus_bytes} B)")
        print(f"parity vs oracle:      {'OK' if parity_ok else 'MISMATCH'}")
        if smoke:
            if not parity_ok:
                raise SystemExit("mmap smoke: answers diverge from the "
                                 "resident oracle")
            if ratio < 10:
                raise SystemExit(f"mmap smoke: corpus only {ratio:.1f}x "
                                 "cache capacity (need >= 10x)")
            if stats["bytes"] > capacity:
                raise SystemExit("mmap smoke: cache over capacity")
            if stats["evictions"] == 0:
                raise SystemExit("mmap smoke: cache never evicted — "
                                 "corpus fit in memory, gate proved "
                                 "nothing")
            if heap_peak >= corpus_bytes:
                raise SystemExit(f"mmap smoke: serving heap peak "
                                 f"{heap_peak} B not bounded below the "
                                 f"{corpus_bytes} B corpus")
        _gauge_build(n_docs, build_s, None)
        return {"build_s": build_s, "serve_p95_ms": 1e3 * p95,
                "corpus_bytes": corpus_bytes, "cache_capacity": capacity,
                "corpus_over_cache": ratio, "heap_peak": heap_peak,
                "cache_hits": stats["hits"], "cache_misses": stats["misses"],
                "cache_evictions": stats["evictions"],
                "parity_ok": parity_ok}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=1500)
    ap.add_argument("--writers", type=int, default=4)
    ap.add_argument("--tiered", action="store_true",
                    help="benchmark the tiered engine (hot build rate + "
                         "compaction pause time)")
    ap.add_argument("--mmap", action="store_true",
                    help="benchmark larger-than-memory serving: mmap v2 "
                         "run + admission-controlled block cache")
    ap.add_argument("--smoke", action="store_true",
                    help="fail loudly on lost docs, an idle compactor, or "
                         "a broken mmap-serving invariant (CI guard)")
    args = ap.parse_args()
    if args.tiered:
        run_tiered(args.docs, smoke=args.smoke)
    elif args.mmap:
        run_mmap(args.docs, smoke=args.smoke)
    else:
        run(args.docs, args.writers)
