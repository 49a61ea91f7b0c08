"""The device scorer's compact form against its postings form and a float64
sum, and the sharded server that packs compact blocks.

``bm25_topk`` scores a flat ``[P]`` block of ``q * n_docs + d`` addresses
the same as the padded ``[Q, T, L]`` block holding the same postings; the
sharded ``RetrievalServer`` sends only such blocks, bucketed so that a
batch past the floor is at least two thirds postings.
"""

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import DynamicIndex, Warren, ingest_documents, score_bm25
from repro.core.vectorized import bm25_topk
from repro.dist.shard_router import ShardedWarren
from repro.train.serve import (POSTINGS_FLOOR, BatcherConfig,
                               RetrievalServer, posting_buckets)

N_DOCS, K = 512, 10


def _case(kind: str, seed: int = 0):
    """(q, t, doc ids, impacts) rows of one batch of 4 queries x 5 terms."""
    rng = np.random.default_rng(seed)
    rows = []
    for q in range(4):
        for t in range(5):
            n = int(rng.integers(1, 200))
            if kind == "empty rows" and (q + t) % 3 == 0:
                n = 0
            docs = np.sort(rng.choice(N_DOCS, n, replace=False))
            imp = rng.random(n).astype(np.float32) + 0.01
            if kind == "equal scores":
                imp[:] = 0.5        # every document of a row ties exactly
            rows.append((q, t, docs, imp))
        if kind == "duplicate stems":   # a stem twice in one query
            _, _, docs, imp = rows[-1]
            rows.append((q, 5, docs, imp))
    if kind == "no postings":
        rows = [(q, t, d[:0], i[:0]) for q, t, d, i in rows]
    if kind == "bucket edge":       # exactly POSTINGS_FLOOR postings
        total, cut = 0, []
        for q, t, d, i in rows:
            n = min(len(d), POSTINGS_FLOOR - total)
            cut.append((q, t, d[:n], i[:n]))
            total += n
        assert total == POSTINGS_FLOOR
        rows = cut
    return rows


def _postings_form(rows, qp=4, tp=8):
    l = max([len(d) for _, _, d, _ in rows] + [1])
    di = np.full((qp, tp, l), N_DOCS, np.int32)
    im = np.zeros((qp, tp, l), np.float32)
    qm = np.zeros((qp, tp), np.float32)
    for q, t, d, i in rows:
        di[q, t, :len(d)] = d
        im[q, t, :len(d)] = i
        qm[q, t] = 1.0
    return di, im, qm


def _compact_form(rows, qp=4):
    n = sum(len(d) for _, _, d, _ in rows)
    p = posting_buckets(n)[-1]
    di = np.full(p, qp * N_DOCS, np.int32)
    im = np.zeros(p, np.float32)
    pos = 0
    for q, _, d, i in rows:
        di[pos:pos + len(d)] = d + q * N_DOCS
        im[pos:pos + len(d)] = i
        pos += len(d)
    return di, im, np.ones((qp, 1), np.float32)


def _exhaustive(rows, qp=4):
    """Float64 sums; the top k by (-score, document index)."""
    acc = np.zeros((qp, N_DOCS))
    for q, _, d, i in rows:
        np.add.at(acc[q], d, i.astype(np.float64))
    ids = np.stack([np.lexsort((np.arange(N_DOCS), -a))[:K] for a in acc])
    return np.take_along_axis(acc, ids, 1), ids


@pytest.mark.parametrize("kind", ["random", "duplicate stems", "empty rows",
                                  "bucket edge", "equal scores",
                                  "no postings"])
def test_compact_form_matches_postings_form_and_float64(kind):
    rows = _case(kind)
    want_s, want_i = _exhaustive(rows)
    got = {}
    for form, args in (("postings", _postings_form(rows)),
                       ("compact", _compact_form(rows))):
        s, i = bm25_topk(*args, n_docs=N_DOCS, k=K)
        got[form] = (np.asarray(s), np.asarray(i))
        np.testing.assert_allclose(got[form][0], want_s, rtol=1e-6)
        live = want_s > 0           # a zero row's order is no ranking
        np.testing.assert_array_equal(got[form][1][live], want_i[live])
    np.testing.assert_array_equal(got["compact"][0], got["postings"][0])
    np.testing.assert_array_equal(got["compact"][1], got["postings"][1])
    if kind == "bucket edge":
        assert len(_compact_form(rows)[0]) == POSTINGS_FLOOR


def test_compact_form_empty_query_slot_scores_zero():
    rows = [r for r in _case("random") if r[0] != 2]
    di, im, qm = _compact_form(rows)
    qm[2:] = 0.0
    s, _ = bm25_topk(di, im, qm, n_docs=N_DOCS, k=K)
    assert not np.asarray(s)[2:].any() and np.asarray(s)[:2].all()


def test_posting_buckets_step_at_most_one_and_a_half():
    sizes = posting_buckets(5_000_000)
    assert sizes[0] == POSTINGS_FLOOR and sizes[-1] >= 5_000_000
    assert all(b % 128 == 0 for b in sizes)
    assert all(a < b <= 1.5 * a for a, b in zip(sizes, sizes[1:]))
    for n in (POSTINGS_FLOOR + 1, 3457, 40_000, 1_234_567):
        p = posting_buckets(n)[-1]
        assert n <= p < 1.5 * n


# ------------------------------------------------------------------ #
# the sharded server: single-index answers, fill share, no late compile
# ------------------------------------------------------------------ #
COMMON = "school education wind river"
ALL = 320


@pytest.fixture(scope="module")
def pair():
    docs = [(f"d{i}", f"{COMMON} item{i} filler{i % 7}") for i in range(300)]
    sharded = ShardedWarren(n_shards=3, replicas=1, async_scatter=True)
    single = Warren(DynamicIndex())
    ingest_documents(sharded, docs, batch=8)
    ingest_documents(single, docs, batch=8)
    with sharded:
        per_group = sharded.map_groups(lambda w: len(w.annotations(":")))
    assert min(per_group) * 4 * 16 > 1.5 * POSTINGS_FLOOR, per_group
    yield sharded, single
    sharded.close()


def _grouped(warren, hits):
    """(score, texts) per score, ties as sets: every document is returned
    (k is past the collection), so no tie is cut at the boundary."""
    docs = warren.annotations(":")
    ends = {int(s): int(e) for s, e in zip(docs.starts, docs.ends)}
    out = {}
    for d, s in hits:
        out.setdefault(round(s, 6), set()).add(warren.translate(d, ends[d]))
    return out


def test_sharded_server_compact_blocks_answer_as_single_index(pair):
    sharded, single = pair
    queries = [f"{COMMON} item{i}" for i in range(16)]
    server = RetrievalServer(sharded, k=ALL, max_terms=8,
                             batcher=BatcherConfig(max_batch=16,
                                                   max_wait_ms=500))
    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    obs.enable()
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        obs.registry().reset()
        got = [h.get(timeout=120)
               for h in [server.batcher.submit(q) for q in queries]]
        reg = obs.registry()
        scored = reg.histogram("serve_scored_postings")
        slots = reg.histogram("serve_scatter_slots")
        assert scored.count == slots.count == 1     # one batch of 16
        assert scored.sum > 3 * POSTINGS_FLOOR
        assert scored.sum / slots.sum >= 1 / 1.5
        assert reg.histogram("serve_h2d_bytes").sum == 8 * slots.sum + \
            3 * 4 * 16      # every group's [P] pair and [16, 1] weights
        # the first batch compiled its width's whole grid: a batch of
        # another size compiles nothing
        compiles.clear()
        assert server.query(queries[3], timeout=60) == got[3]
        assert compiles == []
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        server.close()
    with sharded, single:
        for q, hits in zip(queries, got):
            want = score_bm25(single, q, k=ALL)
            assert len(hits) == len(want) == 300
            assert _grouped(sharded, hits) == _grouped(single, want), q
