"""Aggregate requests served through ``RetrievalServer`` over a replicated
``ShardedWarren`` of ``lineitem`` JSON objects: TPC-H Q1 and Q6 equal
the plain reference exactly, before and after commits that insert and
erase rows, mixed with BM25 queries in one batch."""

import itertools

import pytest

import _tpch as T
from repro import obs
from repro.core import aggregate, json_store, ranking
from repro.core.aggregate import AggregateRequest, Column, Factor, Output
from repro.dist.shard_router import ShardedWarren
from repro.train.serve import BatcherConfig, RetrievalServer

PASSAGES = ["furiously regular deposits sleep quickly",
            "slyly ironic packages haggle against the deposits",
            "carefully final requests nag blithely"]


@pytest.fixture(scope="module")
def rows():
    return T.lineitems(120, seed=16)


@pytest.fixture
def served(rows):
    warren = ShardedWarren(n_shards=3, replicas=2, async_scatter=True)
    T.ingest(warren, rows)
    server = RetrievalServer(warren, batcher=BatcherConfig(max_batch=16,
                                                           max_wait_ms=50))
    was = obs.registry().enabled
    obs.enable()
    yield warren, server, list(rows)
    if not was:
        obs.disable()
    server.close()
    warren.close()


def _serve(server, requests):
    handles = [server.batcher.submit(r) for r in requests]
    return [h.get(timeout=120) for h in handles]


def _builds() -> int:
    return sum(m.value for _, m in
               obs.registry().series("serve_column_builds_total"))


def test_q1_at_both_delta_edges_and_a_q6_grid_are_exact(served):
    warren, server, rows = served
    deltas = [60, 120]
    grid = list(itertools.product((1993, 1995, 1997), (2, 5, 9), (24, 25)))
    got = _serve(server, [T.q1(d) for d in deltas]
                 + [T.q6(*g) for g in grid])
    assert got[:2] == [T.ref_q1(rows, d) for d in deltas]
    assert got[2:] == [T.ref_q6(rows, *g) for g in grid]
    assert all(len(a) == 4 for a in got[:2])     # A/F, N/F, N/O, R/F
    assert any(a[0][0] is not None for a in got[2:])
    # a count that reads no column counts every row, and no padding
    count = AggregateRequest("lineitem", outputs=(Output("n", "count"),))
    assert _serve(server, [count]) == [[(len(rows),)]]


def test_aggregates_and_bm25_queries_share_a_batch(served):
    warren, server, rows = served
    with warren:
        warren.transaction()
        for text in PASSAGES:
            ranking.index_document(warren, text)
        warren.commit()
    texts = ["deposits", "final requests"]
    alone = _serve(server, texts)
    requests = [texts[0], T.q1(90), T.q6(1994, 6, 24), texts[1], T.q1(90)]
    got = server._handle(requests)         # one micro-batch, as the loop
    assert got[0] == alone[0] and got[3] == alone[1]
    assert got[0] and got[3]
    assert got[1] == got[4] == T.ref_q1(rows, 90)
    assert got[2] == T.ref_q6(rows, 1994, 6, 24)
    # and through the batcher, in flight together
    assert _serve(server, requests) == got


def test_commits_move_the_snapshot_key_and_rebuild_the_columns(served):
    warren, server, rows = served
    requests = [T.q1(75), T.q6(1996, 7, 25)]
    assert _serve(server, requests) == [T.ref_q1(rows, 75),
                                        T.ref_q6(rows, 1996, 7, 25)]
    before = _builds()
    _serve(server, requests)
    assert _builds() == before               # the key held: no build
    # RF1-like: new orders' rows, inserted and dated in their own commits
    new = T.lineitems(12, seed=99)
    with warren:
        warren.transaction()
        for r in new:
            json_store.add_json(warren, r, collection="lineitem")
        warren.commit()
    with warren:
        warren.transaction()
        json_store.annotate_dates(warren, T.DATES)
        warren.commit()
    rows = rows + new
    assert _serve(server, requests) == [T.ref_q1(rows, 75),
                                        T.ref_q6(rows, 1996, 7, 25)]
    assert _builds() > before
    # RF2-like: erase every row of a few orders
    gone = {r["l_orderkey"] for r in rows[:9]}
    with warren:
        extents = list(zip(warren.annotations("lineitem").starts,
                           warren.annotations("lineitem").ends))
        keys = warren.annotations(":l_orderkey:")
        warren.transaction()
        for (p, q), (_, _, key) in zip(extents, keys):
            if int(key) in gone:
                warren.erase(int(p), int(q))
        warren.commit()
    rows = [r for r in rows if r["l_orderkey"] not in gone]
    before = _builds()
    assert _serve(server, requests) == [T.ref_q1(rows, 75),
                                        T.ref_q6(rows, 1996, 7, 25)]
    assert _builds() > before


def test_a_row_lacking_a_path_is_masked(served):
    warren, server, rows = served
    odd = dict(rows[0], l_orderkey=999_999, l_shipdate="1994-02-03",
               l_discount=0.06, l_quantity=3)
    del odd["l_tax"]
    with warren:
        warren.transaction()
        json_store.add_json(warren, odd, collection="lineitem")
        warren.commit()
    with warren:
        warren.transaction()
        json_store.annotate_dates(warren, T.DATES)
        warren.commit()
    # Q1 reads l_tax, so the row is not counted; Q6 does not, so it is
    assert _serve(server, [T.q1(60)])[0] == T.ref_q1(rows, 60)
    assert _serve(server, [T.q6(1994, 6, 24)])[0] == \
        T.ref_q6(rows + [odd], 1994, 6, 24)
    assert T.ref_q6(rows + [odd], 1994, 6, 24) != T.ref_q6(rows, 1994, 6, 24)


def test_day_numbers_match_parse_date(served):
    warren, _, rows = served
    with warren:
        for path in T.DATES:
            dates = warren.annotations(path)
            days = warren.annotations(json_store.days_path(path))
            assert len(days) == len(dates) == len(rows)
            for (p, q, _), (dp, dq, v) in zip(dates, days):
                assert (dp, dq) == (p, q)
                ymd = json_store.parse_date(
                    json_store.raw_value_of(warren, int(p), int(q)))
                assert v == json_store.day_number(*ymd)


def test_columns_are_exact_fixed_point(served):
    warren, _, rows = served
    with warren:
        cols = warren.map_groups(lambda w: json_store.extract_columns(
            w, "lineitem", {":l_extendedprice:": 2, ":l_returnflag:": None}))
    got = sorted(int(v) for c in cols for v in c.values[":l_extendedprice:"])
    assert got == sorted(round(r["l_extendedprice"] * 100) for r in rows)
    assert {f for c in cols for f in c.codes[":l_returnflag:"]} == \
        {r["l_returnflag"] for r in rows}
    with warren, pytest.raises(ValueError, match="decimals"):
        warren.map_groups(lambda w: json_store.extract_columns(
            w, "lineitem", {":l_extendedprice:": 1}))


@pytest.mark.parametrize("bad", [
    dict(group_by=(T.QTY,)),
    dict(where=(aggregate.Range(T.FLAG, 0, 1),)),
    dict(outputs=(Output("n", "count", (Factor(T.QTY),)),)),
    dict(outputs=(Output("s", "sum", (Factor(T.FLAG),)),)),
    dict(outputs=(Output("s", "median", (Factor(T.QTY),)),)),
    dict(outputs=(Output("s", "sum", (Factor(T.QTY),) * 4),)),
    dict(outputs=(Output("s", "sum", (Factor(Column(":l_quantity:", 2)),)),
                  Output("t", "sum", (Factor(T.QTY),)))),
])
def test_requests_outside_the_form_are_refused(bad):
    with pytest.raises(ValueError):
        AggregateRequest("lineitem", **bad)


def test_a_sum_that_could_leave_64_bits_is_refused(served):
    warren, server, _ = served
    huge = Factor(T.PRICE, coef=2**31 - 1)
    req = AggregateRequest("lineitem", outputs=(
        Output("s", "sum", (huge, huge, huge)),))
    with pytest.raises(OverflowError):
        _serve(server, [req])
