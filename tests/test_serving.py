"""Serving-path regression + async scatter-gather tests.

Covers the four serving bugfixes (batcher thread death on a handler
exception, posting-cap truncation by doc order instead of impact, stale KV
cache across ``LMServer.generate`` calls, eager materialization in the
sharded gather) and the ``repro.dist.parallel`` scatter-gather executor:
pool-based per-group fan-out must be result-identical to the sequential
loop — including under replica failover — and the native sharded
``RetrievalServer`` must match a single-index server bit-for-bit.
"""

import threading
import time

import numpy as np
import pytest

from repro.core import (DynamicIndex, Warren, collection_stats,
                        index_document, ingest_documents, score_bm25)
from repro.data.synth import doc_generator
from repro.dist.parallel import ScatterGather, ScatterTimings
from repro.dist.shard_router import ShardedWarren
from repro.train.serve import BatcherConfig, MicroBatcher, RetrievalServer


# ------------------------------------------------------------------ #
# repro.dist.parallel: the executor itself
# ------------------------------------------------------------------ #
def test_scatter_gather_preserves_order():
    with ScatterGather(workers=4) as sg:
        def slow_identity(i):
            time.sleep(0.02 * (5 - i) / 5)     # later items finish first
            return i
        assert sg.map(slow_identity, range(5)) == [0, 1, 2, 3, 4]


def test_scatter_gather_runs_all_then_raises_first():
    ran = []
    lock = threading.Lock()

    def job(i):
        with lock:
            ran.append(i)
        if i in (1, 3):
            raise ValueError(f"job {i}")
        return i

    with ScatterGather(workers=2) as sg:
        with pytest.raises(ValueError, match="job 1"):
            sg.map(job, range(5))
    assert sorted(ran) == [0, 1, 2, 3, 4]      # no job was cancelled


def test_scatter_gather_closed_falls_back_to_sequential():
    sg = ScatterGather(workers=2)
    sg.close()
    assert sg.map(lambda i: i * i, range(4)) == [0, 1, 4, 9]


def test_scatter_timings_accumulate_and_reset():
    t = ScatterTimings()
    t.add(scatter=0.5, score=0.25, merge=0.25, queries=2)
    snap = t.snapshot()
    assert snap["queries"] == 2 and snap["scatter_s"] == 0.5
    assert "scatter" in t.summary() and "merge" in t.summary()
    t.reset()
    assert t.snapshot()["queries"] == 0


# ------------------------------------------------------------------ #
# bugfix: a handler exception must not kill the batcher thread
# ------------------------------------------------------------------ #
def test_microbatcher_survives_handler_exception():
    def handler(batch):
        if any(req == "poison" for req in batch):
            raise ValueError("bad batch")
        return [req.upper() for req in batch]

    mb = MicroBatcher(handler, BatcherConfig(max_batch=1, max_wait_ms=0.5))
    try:
        ok = mb.submit("first")
        assert ok.get(timeout=5) == "FIRST"
        # the poisoned batch fails ITS waiter with the handler's exception…
        poisoned = mb.submit("poison")
        with pytest.raises(ValueError, match="bad batch"):
            poisoned.get(timeout=5)
        # …and the loop is still alive for every later request
        for i in range(3):
            assert mb.submit(f"req{i}").get(timeout=5) == f"REQ{i}"
    finally:
        mb.close()


def test_microbatcher_close_fails_queued_waiters():
    release = threading.Event()

    def handler(batch):
        release.wait(5)
        return list(batch)

    mb = MicroBatcher(handler, BatcherConfig(max_batch=1, max_wait_ms=0.1))
    h1 = mb.submit("a")
    time.sleep(0.1)                   # the loop takes "a" into the handler
    h2 = mb.submit("b")               # still queued behind it
    closer = threading.Thread(target=mb.close)
    closer.start()
    time.sleep(0.05)
    release.set()
    closer.join()
    assert h1.get(timeout=5) == "a"   # the in-flight batch still completes
    with pytest.raises(RuntimeError, match="closed"):
        h2.get(timeout=5)             # queued waiter fails promptly
    with pytest.raises(RuntimeError, match="closed"):
        mb.submit("c").get(timeout=5)  # post-close submits fail fast


def test_microbatcher_result_count_mismatch_fails_batch():
    mb = MicroBatcher(lambda batch: [], BatcherConfig(max_batch=1))
    try:
        with pytest.raises(RuntimeError, match="results"):
            mb.submit("x").get(timeout=5)
    finally:
        mb.close()


# ------------------------------------------------------------------ #
# bugfix: the posting cap keeps top-impact postings, not doc-order ones
# ------------------------------------------------------------------ #
def test_posting_cap_keeps_high_impact_doc():
    warren = Warren(DynamicIndex())
    with warren:
        warren.transaction()
        for i in range(8):                      # tf=1 fodder, low impact
            index_document(warren, f"zzz filler{i} pad", docid=f"low{i}")
        # the BEST document for "zzz" sits PAST the posting cap in doc order
        index_document(warren, "zzz zzz zzz zzz", docid="best")
        warren.commit()
    with warren:
        oracle = score_bm25(warren, "zzz", k=3)
        docs = warren.annotations(":")
        ends = {int(s): int(e) for s, e in zip(docs.starts, docs.ends)}
        best_addr = oracle[0][0]
        assert "zzz zzz" in warren.translate(best_addr, ends[best_addr])
    server = RetrievalServer(warren, k=3, max_postings=4)
    try:
        res = server.query("zzz", timeout=30)
        assert res[0][0] == best_addr
        # device path scores in float32; the oracle in float64
        np.testing.assert_allclose(res[0][1], oracle[0][1], rtol=1e-6)
    finally:
        server.close()


# ------------------------------------------------------------------ #
# bugfix: LMServer must not decode against a previous call's KV cache
# ------------------------------------------------------------------ #
def test_lmserver_two_call_parity():
    import dataclasses

    import jax

    from repro.configs import get_arch
    from repro.train.serve import LMServer

    spec = get_arch("internlm2-1.8b")
    cfg = dataclasses.replace(spec.smoke_config, dtype="float32")
    params = spec.init_fn(cfg, jax.random.PRNGKey(0))
    server = LMServer(params, cfg, max_slots=2, max_len=16)
    prompts = [[5, 9, 2], [7, 4]]
    first = server.generate(prompts, max_new=4)
    second = server.generate(prompts, max_new=4)
    assert first == second
    assert all(len(o) == 4 for o in first)


# ------------------------------------------------------------------ #
# sharded serving: fixtures
# ------------------------------------------------------------------ #
def _ingest(warren, docs, batch=16):
    ingest_documents(warren, docs, batch=batch)


def _grouped_hits(warren, hits):
    """(rounded score, text) with equal-score ties grouped as frozensets —
    address layouts differ between sharded and single warrens by design."""
    docs = warren.annotations(":")
    ends = {int(s): int(e) for s, e in zip(docs.starts, docs.ends)}
    pairs = [(round(s, 9), warren.translate(d, ends[d])) for d, s in hits]
    groups, i = [], 0
    while i < len(pairs):
        j = i
        while j < len(pairs) and pairs[j][0] == pairs[i][0]:
            j += 1
        groups.append((pairs[i][0], frozenset(t for _, t in pairs[i:j])))
        i = j
    return groups


QUERIES = ["school education student", "government law state",
           "stock money business", "vibration conductor wind"]


@pytest.fixture(scope="module")
def serving_pair():
    corpus = list(doc_generator(7, 150, mean_len=40))
    sharded = ShardedWarren(n_shards=3, replicas=2, async_scatter=True)
    single = Warren(DynamicIndex())
    _ingest(sharded, corpus)
    _ingest(single, corpus)
    yield sharded, single
    sharded.close()


# ------------------------------------------------------------------ #
# async scatter == sequential scatter, failover preserved
# ------------------------------------------------------------------ #
def test_async_scatter_matches_sequential_reads(serving_pair):
    sharded, single = serving_pair
    assert sharded.async_scatter
    with sharded:
        async_res = {q: score_bm25(sharded, q, k=10) for q in QUERIES}
        async_docs = len(sharded.annotations(":"))
        async_stats = sharded.map_groups(collection_stats)
        async_gcl = sharded.search_gcl("school", limit=10_000)
    sharded.set_async_scatter(False)
    try:
        with sharded:
            for q in QUERIES:
                assert score_bm25(sharded, q, k=10) == async_res[q]
            assert len(sharded.annotations(":")) == async_docs
            seq_stats = sharded.map_groups(collection_stats)
            assert [s.n_docs for s in seq_stats] == \
                [s.n_docs for s in async_stats]
            assert [s.avgdl for s in seq_stats] == \
                [s.avgdl for s in async_stats]
            for seq, asy in zip(seq_stats, async_stats):
                np.testing.assert_array_equal(seq.doc_starts, asy.doc_starts)
            assert sharded.search_gcl("school", limit=10_000) == async_gcl
    finally:
        sharded.set_async_scatter(True)
    with single:
        for q in QUERIES:
            ref = _grouped_hits(single, score_bm25(single, q, k=10))
            with sharded:
                got = _grouped_hits(sharded, async_res[q])
            assert got == ref, q


def test_async_scatter_failover_inside_workers(serving_pair):
    sharded, single = serving_pair
    for g in range(sharded.n_shards):
        sharded.mark_failed(g, g % 2)
    try:
        with sharded, single:
            for q in QUERIES:
                assert _grouped_hits(sharded,
                                     score_bm25(sharded, q, k=10)) == \
                    _grouped_hits(single, score_bm25(single, q, k=10)), q
    finally:
        for g in range(sharded.n_shards):
            sharded.resurrect(g, g % 2)


def test_search_records_timing_breakdown(serving_pair):
    sharded, _ = serving_pair
    server = RetrievalServer(sharded, k=10)
    try:
        server.query(QUERIES[0], timeout=60)
        snap = server.timings.snapshot()
        assert snap["queries"] == 1
        assert snap["scatter_s"] > 0 and snap["score_s"] > 0
        assert snap["merge_s"] > 0
        assert "ms/query" in server.timings.summary()
    finally:
        server.close()


# ------------------------------------------------------------------ #
# bugfix: gather is lazy (islice) and exact at a tie on the k boundary
# ------------------------------------------------------------------ #
def test_sharded_search_tie_at_k_boundary():
    sharded = ShardedWarren(n_shards=3)
    single = Warren(DynamicIndex())
    docs = [(f"hi{i}", "school school education education") for i in range(3)]
    # 14 docs tied exactly (same tf, same dl, different filler terms so the
    # hash router spreads them over groups) — the k=10 boundary falls
    # INSIDE the tie group
    docs += [(f"tie{i}", f"school education filler{i}") for i in range(14)]
    docs += [(f"noise{i}", "stock money business") for i in range(6)]
    for docid, text in docs:                    # one txn per doc: spread out
        with sharded:
            sharded.transaction()
            index_document(sharded, text, docid=docid)
            sharded.commit()
        with single:
            single.transaction()
            index_document(single, text, docid=docid)
            single.commit()
    assert sum(len(g.replicas[0]._segments) > 0 for g in sharded.groups) > 1
    server = RetrievalServer(sharded, k=10)
    try:
        got = server.query("school education", timeout=60)
    finally:
        server.close()
    with sharded, single:
        ref = score_bm25(sharded, "school education", k=10)
        assert len(got) == len(ref) == 10
        # the served top-k is the reference's, tie members included: at
        # the k boundary both keep the lowest addresses of the tie class
        assert [d for d, _ in got] == [d for d, _ in ref]
        np.testing.assert_allclose([s for _, s in got],
                                   [s for _, s in ref], rtol=1e-6)
        single_ref = score_bm25(single, "school education", k=10)
        assert [round(s, 9) for _, s in ref] == \
            [round(s, 9) for _, s in single_ref]
        # addresses are striped, so which tie members sit below the k
        # boundary differs from the single index by design — every returned
        # member must belong to the single-index tie class
        ref_all = score_bm25(single, "school education", k=25)
        classes = {}
        for score, texts in _grouped_hits(single, ref_all):
            classes[score] = texts
        for score, texts in _grouped_hits(sharded, ref):
            assert texts <= classes[score], score


# ------------------------------------------------------------------ #
# acceptance: native sharded RetrievalServer == single-index server
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("max_postings", [4096, 8])
def test_sharded_server_matches_single_server(serving_pair, max_postings):
    """The micro-batched scatter/score/merge pipeline returns the same
    (score, text) ranking over three groups as over one (a plain warren,
    served as a single group) — including with a tight posting cap, where
    the cap must bind to the GLOBAL top-impact postings, not per-group or
    doc-order ones."""
    sharded, single = serving_pair
    srv_sharded = RetrievalServer(sharded, k=10, max_postings=max_postings)
    srv_single = RetrievalServer(single, k=10, max_postings=max_postings)
    try:
        handles = [srv_sharded.batcher.submit(q) for q in QUERIES * 2]
        got = [h.get(timeout=60) for h in handles]
        ref = [srv_single.query(q, timeout=60) for q in QUERIES * 2]
        with sharded, single:
            for q, g_hits, r_hits in zip(QUERIES * 2, got, ref):
                assert _grouped_hits(sharded, g_hits) == \
                    _grouped_hits(single, r_hits), q
                np.testing.assert_allclose([s for _, s in g_hits],
                                           [s for _, s in r_hits], rtol=1e-9)
        assert srv_sharded.timings.snapshot()["queries"] >= len(QUERIES)
    finally:
        srv_sharded.close()
        srv_single.close()


def test_sharded_server_over_demoted_group(tmp_path):
    """The native scatter path reads demoted groups through their static
    run sets: results match a fully hot sharded warren."""
    corpus = list(doc_generator(11, 90, mean_len=30))
    sharded = ShardedWarren(n_shards=3, static_dir=str(tmp_path),
                            async_scatter=True)
    single = Warren(DynamicIndex())
    _ingest(sharded, corpus)
    _ingest(single, corpus)
    try:
        sharded.demote_group(1)
        server = RetrievalServer(sharded, k=10)
        oracle = RetrievalServer(single, k=10)
        try:
            hits = [(server.query(q, timeout=60), oracle.query(q, timeout=60))
                    for q in QUERIES[:2]]
            with sharded, single:
                for q, (got, ref) in zip(QUERIES[:2], hits):
                    assert _grouped_hits(sharded, got) == \
                        _grouped_hits(single, ref), q
        finally:
            server.close()
            oracle.close()
    finally:
        sharded.close()


def test_sharded_server_stats_refresh_after_commit(serving_pair):
    """The native path re-scatters stats per batch: documents committed
    after server construction are immediately retrievable."""
    sharded, _ = serving_pair
    server = RetrievalServer(sharded, k=5)
    try:
        with sharded:
            sharded.transaction()
            index_document(sharded, "xylophone quartz unique",
                           docid="fresh-doc")
            sharded.commit()
        res = server.query("xylophone quartz", timeout=30)
        assert len(res) == 1
        with sharded:
            docs = sharded.annotations(":")
            ends = {int(s): int(e) for s, e in zip(docs.starts, docs.ends)}
            assert "xylophone" in sharded.translate(res[0][0],
                                                    ends[res[0][0]])
        # clean up so the module-scoped corpus stays stable for other tests
        with sharded:
            sharded.transaction()
            sharded.erase(res[0][0], ends[res[0][0]])
            sharded.commit()
    finally:
        server.close()


def test_plain_warren_server_sees_commit_without_refresh():
    """A plain warren is served as one group through the same per-batch
    fan-out: a commit after construction shows in the next answer, which
    equals ``score_bm25`` on the new snapshot, with no refresh call."""
    warren = Warren(DynamicIndex())
    _ingest(warren, doc_generator(5, 40, mean_len=20))
    server = RetrievalServer(warren, k=10)
    try:
        query = "school education quartz"
        with warren:
            before = score_bm25(warren, query, k=10)
        assert server.query(query, timeout=60)[0][0] == before[0][0]
        with warren:
            warren.transaction()
            index_document(warren, "quartz quartz school education",
                           docid="fresh")
            warren.commit()
        got = server.query(query, timeout=60)
        with warren:
            ref = score_bm25(warren, query, k=10)
            fresh = warren.annotations("docid:fresh")
        assert got[0][0] == ref[0][0] == int(fresh.starts[0])
        assert [d for d, _ in got] == [d for d, _ in ref]
        np.testing.assert_allclose([s for _, s in got],
                                   [s for _, s in ref], rtol=1e-6)
    finally:
        server.close()
