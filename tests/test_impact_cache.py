"""``RetrievalServer`` keeps each stem's BM25 impacts for the snapshot they
were computed on: a batch under the same snapshot signature reuses them,
and a commit, erase or rebalance drops them.  Every served list is held to
``score_bm25`` on the same snapshot, list for list."""

import numpy as np
import pytest

from repro import obs
from repro.core import (DynamicIndex, Warren, index_document,
                        ingest_documents, ranking, score_bm25)
from repro.data.synth import doc_generator
from repro.dist.rebalance import Rebalancer
from repro.dist.shard_router import ShardedWarren
from repro.tiered import TieredStore
from repro.train.serve import RetrievalServer

# "stock", "the", "of" and "and" are in no document: their stems are
# cached as held by no group
QUERIES = ["school education student", "government law state",
           "stock money business", "the of and school",
           "vibration conductor wind"]
# the batch's distinct stems, as the server features them
STEMS = list(dict.fromkeys(
    ranking.TF_PREFIX + ranking.porter_stem(t)
    for q in QUERIES for t in ranking.ranking_tokens(q)))


@pytest.fixture(autouse=True)
def metrics():
    was = obs.registry().enabled
    obs.enable()
    yield
    if not was:
        obs.disable()


def _counts() -> dict:
    """(metric, result) -> value of the impact cache's counters."""
    out = {}
    for name in ("serve_impact_stems_total", "serve_impact_postings_total"):
        for labels, m in obs.registry().series(name):
            out[name, dict(labels)["result"]] = m.value
    return out


def _delta(before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _counts().items()}


def _corpus(warren):
    ingest_documents(warren, doc_generator(7, 150, mean_len=40), batch=16)
    return warren


def _sharded(tmp_path=None):
    sharded = _corpus(ShardedWarren(n_shards=3, replicas=2,
                                    async_scatter=True))
    return sharded, sharded.close


def _plain(tmp_path):
    return _corpus(Warren(DynamicIndex())), lambda: None


def _tiered(tmp_path):
    """Cold runs with an empty hot tier on top: a commit lands in the hot
    tier over them."""
    store = TieredStore(str(tmp_path / "t"))
    warren = _corpus(store.warren())
    store.freeze()
    return warren, store.close


def _assert_exact(warren, got, k: int = 10) -> None:
    with warren:
        ref = [score_bm25(warren, q, k=k) for q in QUERIES]
    for q, g_hits, r_hits in zip(QUERIES, got, ref):
        assert g_hits, q
        assert [d for d, _ in g_hits] == [d for d, _ in r_hits], q
        # the device scores in float32, the reference in float64
        np.testing.assert_allclose([s for _, s in g_hits],
                                   [s for _, s in r_hits], rtol=1e-6)


def _largest_df(warren) -> int:
    with warren:
        return max(len(warren.annotations(f)) for f in STEMS)


@pytest.mark.parametrize("capped", [False, True], ids=["uncapped", "capped"])
def test_second_identical_batch_is_served_from_the_cache(capped):
    """Two identical batches in a row: the second computes no stem and
    returns the first's lists to the bit, both equal to ``score_bm25`` —
    with the posting cap above the largest df and below it, so the
    capped lists are cached too."""
    sharded, close = _sharded()
    largest = _largest_df(sharded)
    # one below the largest df, the cap drops the lowest-impact posting of
    # the commonest stems, which reaches no top-10 of this corpus (a lower
    # cap changes answers, as a posting cap may)
    cap = largest - 1 if capped else 4 * largest
    server = RetrievalServer(sharded, k=10, max_postings=cap)
    try:
        before = _counts()
        first = server._handle(QUERIES)
        one = _delta(before)
        assert one["serve_impact_stems_total", "miss"] == len(STEMS)
        assert one["serve_impact_stems_total", "hit"] == 0
        before = _counts()
        second = server._handle(QUERIES)
        two = _delta(before)
        assert two["serve_impact_stems_total", "hit"] == len(STEMS)
        assert two["serve_impact_stems_total", "miss"] == 0
        assert two["serve_impact_postings_total", "miss"] == 0
        assert (two["serve_impact_postings_total", "hit"]
                == one["serve_impact_postings_total", "miss"] > largest)
        assert second == first
        _assert_exact(sharded, second)
        gauge = next(m for _, m in obs.registry().series(
            "serve_impact_cache_bytes"))
        assert gauge.value == server._impacts.nbytes > 0
        if capped:
            kept = sum(len(di) for f, per_g in
                       server._impacts.lookup(server._impacts.signature,
                                              STEMS).items()
                       if per_g is not None for di, _ in per_g)
            assert kept < one["serve_impact_postings_total", "miss"]
    finally:
        server.close()
        close()


def _commit_new_version(warren) -> None:
    """Erase the passage that ranks first for QUERIES[0] and append a new
    version of it that repeats a cached stem."""
    with warren:
        addr = score_bm25(warren, QUERIES[0], k=1)[0][0]
        docs = warren.annotations(":")
        end = int(docs.ends[np.searchsorted(docs.starts, addr)])
        text = warren.translate(addr, end)
        warren.transaction()
        warren.erase(addr, end)
        index_document(warren, text + " government government",
                       docid="new-version")
        warren.commit()


def _split(warren) -> None:
    with warren:
        docs = warren.map_groups(lambda w: len(w.annotations(":")))
    Rebalancer(warren).split_group(int(np.argmax(docs)))


@pytest.mark.parametrize("make, move", [
    (_sharded, _commit_new_version),
    (_plain, _commit_new_version),
    (_tiered, _commit_new_version),
    (_sharded, _split),
], ids=["sharded-commit", "plain-commit", "tiered-commit", "sharded-split"])
def test_a_new_snapshot_drops_every_cached_impact(make, move, tmp_path):
    """A commit (an erase and a new version holding a cached stem) or a
    split between two batches moves the signature: the next batch
    computes every stem again and equals ``score_bm25`` on the new
    snapshot, so no stale impact survives."""
    warren, close = make(tmp_path)
    server = RetrievalServer(warren, k=10)
    try:
        first = server._handle(QUERIES)
        _assert_exact(warren, first)
        signature = server._impacts.signature
        move(warren)
        before = _counts()
        got = server._handle(QUERIES)
        moved = _delta(before)
        assert server._impacts.signature != signature
        assert moved["serve_impact_stems_total", "hit"] == 0
        assert moved["serve_impact_stems_total", "miss"] == len(STEMS)
        _assert_exact(warren, got)
        # a commit changes answers; a split moves no document
        assert (got != first) == (move is _commit_new_version)
    finally:
        server.close()
        close()


def test_eviction_past_the_byte_bound_keeps_the_answers():
    """With a byte bound below one batch's impacts, the least recently
    used stems go, the cache stays under the bound, and every batch still
    equals ``score_bm25``."""
    sharded, close = _sharded()
    probe = RetrievalServer(sharded, k=10)
    server = RetrievalServer(sharded, k=10)
    try:
        first = probe._handle(QUERIES)
        limit = probe._impacts.nbytes // 3
        server._impacts.limit = limit
        for order in (QUERIES, QUERIES[::-1], QUERIES):
            before = _counts()
            got = server._handle(order)
            assert got == (first if order is QUERIES else first[::-1])
            assert 0 < server._impacts.nbytes <= limit
            # what the bound evicted is computed again
            assert _delta(before)["serve_impact_stems_total", "miss"] > 0
        _assert_exact(sharded, got)
    finally:
        probe.close()
        server.close()
        close()
