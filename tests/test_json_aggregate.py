"""Aggregate requests served through ``RetrievalServer`` over a replicated
``ShardedWarren`` of ``lineitem`` JSON objects: TPC-H Q1 and Q6 equal
the plain reference exactly, before and after commits that insert and
erase rows, mixed with BM25 queries in one batch."""

import itertools
from decimal import Decimal
from fractions import Fraction

import numpy as np
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


def test_a_mixed_batch_records_its_distinct_products(served):
    _, server, _ = served
    hist = lambda: next(m for _, m in obs.registry().series(  # noqa: E731
        "serve_agg_products"))
    server._handle([T.q1(90), T.q6(1994, 6, 24), T.q1(60)])
    count, total = hist().count, hist().sum
    server._handle([T.q1(90), T.q6(1994, 6, 24), T.q1(60)])
    # Q1: qty, price, price(1-disc), price(1-disc)(1+tax), disc; Q6:
    # price*disc; 128 a batch in a scan of MAX_SUMS a slot
    assert (hist().count - count, hist().sum - total) == (1, 6)


# -- the device scan against Python integers, on synthetic tables -------- #

X = tuple(Column(f":x{i}:", 2 if i == 2 else 0) for i in range(3))
K = (Column(":k0:", None), Column(":k1:", None))


def _edge(n: int) -> int:
    """The largest ``t`` with ``n * t**3`` under 2^63."""
    t = round((2 ** 63 / n) ** (1 / 3))
    while n * t ** 3 >= 2 ** 63:
        t -= 1
    while n * (t + 1) ** 3 < 2 ** 63:
        t += 1
    return t


def _sum(name, *factors, op="sum"):
    return Output(name, op, tuple(factors))


def _req(*outputs, where=(), group_by=()):
    return AggregateRequest("t", where=tuple(where),
                            group_by=tuple(group_by), outputs=outputs)


T63 = _edge(1000)
F0, F1, F2 = (Factor(x) for x in X)
NEG = Factor(X[0], -3, -7), Factor(X[1], 2, -100), Factor(X[2], -1, 0)
SCAN_CASES = {
    "negative_coefs_and_offsets": dict(n=3000, requests=[
        _req(_sum("a", NEG[0], NEG[1]), _sum("b", NEG[2], op="avg"),
             Output("n", "count"),
             where=[aggregate.Range(X[0], -2000, 3000)]),
        _req(_sum("c", *NEG), group_by=[K[0]])]),
    "bound_just_under_2^63": dict(
        n=1000, vals=((T63, T63), (-T63, -T63), (T63, T63)), requests=[
            _req(_sum("pos", F0, F0, F2), _sum("neg", F0, F1, F2)),
            _req(_sum("neg", F1, F1, F1), group_by=[K[1]])]),
    "absent_values": dict(n=3000, absent=0.3, requests=[
        _req(_sum("a", F0), Output("n", "count"), group_by=[K[0]]),
        _req(_sum("b", F1, F2, op="avg"),
             where=[aggregate.Range(X[2], None, 0)])]),
    "padding_rows": dict(n=1000, requests=[
        _req(Output("n", "count")),
        _req(_sum("a", Factor(X[0], 3, 7), Factor(X[1], 0, -9)))]),
    "several_row_blocks": dict(n=20000, requests=[
        _req(_sum("a", F0, F1), Output("n", "count"),
             where=[aggregate.Range(X[1], -500, None)], group_by=K),
        _req(_sum("b", NEG[0], NEG[2]))]),
    "several_group_keys": dict(n=3000, cards=(5, 7), requests=[
        _req(_sum("a", F0), _sum("b", F2, op="avg"), group_by=K),
        _req(Output("n", "count"), group_by=[K[1], K[0]])]),
    "one_slot_used": dict(n=2000, requests=[
        _req(_sum("a", F0, F1, F2), where=[aggregate.Range(X[0], 0, 0)])]),
    "shared_and_own_products": dict(n=3000, requests=[
        _req(_sum("a", F0, F1), _sum("b", F2)),
        _req(_sum("b", F2), _sum("c", NEG[0]), group_by=[K[0]]),
        _req(_sum("d", F1, NEG[1])),
        _req(_sum("e", NEG[2], F0, F0))]),
    "sixteen_product_slots": dict(n=2000, requests=[
        _req(*(_sum(f"s{i}", Factor(X[i % 3], i - 4, 3 * i - 11))
               for i in range(8))),
        _req(*(_sum(f"t{i}", Factor(X[i % 3], i + 1), Factor(X[2], -1, i))
               for i in range(4)))]),
}


def _columns(rng, n, vals, absent, cards):
    """One group's synthetic columns: path -> (values, present, codes).
    Each group codes its own subset of the strings, as a group would."""
    cols = {}
    for x, (lo, hi) in zip(X, vals):
        cols[x.path] = (rng.integers(lo, hi, n, endpoint=True),
                        rng.random(n) >= absent, None)
    for k, card in zip(K, cards):
        codes = tuple(f"{k.path}{i}" for i in
                      sorted(rng.choice(card + 2, card, replace=False)))
        cols[k.path] = (rng.integers(0, card, n),
                        rng.random(n) >= absent, codes)
    return cols


def _extract(cols, collection, paths):
    n = len(next(iter(cols.values()))[0])
    out = json_store.Columns(np.zeros((n, 2), np.int64), {}, {}, {})
    for p in paths:
        v, present, codes = cols[p]
        out.values[p], out.present[p] = np.where(present, v, 0), present
        if codes is not None:
            out.codes[p] = codes
    return out


def _python_answer(groups, request):
    """``request`` over ``groups`` row by row in Python integers."""
    products = request.products
    acc = {}
    for cols in groups:
        for i in range(len(next(iter(cols.values()))[0])):
            if not all(cols[c.path][1][i] for c in request.columns):
                continue
            if not all((r.lo is None or cols[r.column.path][0][i] >= r.lo)
                       and (r.hi is None
                            or cols[r.column.path][0][i] <= r.hi)
                       for r in request.where):
                continue
            key = tuple(cols[c.path][2][cols[c.path][0][i]]
                        for c in request.group_by)
            tot = acc.setdefault(key, [0] * (len(products) + 1))
            for j, p in enumerate(products):
                v = 1
                for f in p:
                    v *= f.offset + f.coef * int(cols[f.column.path][0][i])
                tot[j] += v
            tot[-1] += 1
    if not request.group_by and not acc:
        acc[()] = [0] * (len(products) + 1)
    rows = []
    for key in sorted(acc):
        tot = acc[key]
        out = list(key)
        for o in request.outputs:
            if o.op == "count":
                out.append(tot[-1])
                continue
            s = tot[products.index(o.factors)]
            scale = sum(f.column.scale for f in o.factors)
            out.append(None if tot[-1] == 0
                       else Decimal(f"{s}E-{scale}") if o.op == "sum"
                       else Fraction(s, 10 ** scale * tot[-1]))
        rows.append(tuple(out))
    return rows


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_the_scan_equals_python_integers(case, monkeypatch):
    """Two groups' tables, scanned by :func:`aggregate.agg_scan` and
    merged by :func:`aggregate.finalize`, equal the same requests over
    the same rows in Python integers, and unused slots hold nothing."""
    spec = {"vals": ((-5000, 5000),) * 3, "absent": 0.0, "cards": (3, 4),
            **SCAN_CASES[case]}
    rng = np.random.default_rng(sorted(SCAN_CASES).index(case) + 1017)
    groups = [_columns(rng, spec["n"] - 7 * g, spec["vals"], spec["absent"],
                       spec["cards"]) for g in range(2)]
    monkeypatch.setattr(json_store, "extract_columns", _extract)
    specs = sorted(set(X) | set(K), key=lambda c: c.path)
    tables = [aggregate.ResidentColumns(g, cols, "t", specs)
              for g, cols in enumerate(groups)]
    requests, slots = spec["requests"], 16
    products = aggregate.batch_products(requests)
    n_keys = aggregate.key_slots(tables, requests)
    parts = aggregate.fetch([aggregate.scan(
        t, *aggregate.encode(requests, t, slots, products), n_keys)
        for t in tables])
    for part in parts:
        assert part.shape == (slots, n_keys, 1 + max(
            aggregate.PRODUCTS_FLOOR, 1 << (len(products) - 1).bit_length()))
        assert not part[len(requests):].any()
    for slot, r in enumerate(requests):
        got = aggregate.finalize(r, list(zip(tables, parts)), slot, products)
        assert got == _python_answer(groups, r)


def test_encode_refuses_past_the_limb_rows_and_64_bits(monkeypatch):
    """A table of more rows than an int32 limb sum can hold is refused,
    whatever the request; so is a sum whose bound reaches 2^63, just."""
    n = 1000
    cols = _columns(np.random.default_rng(5), n, ((T63, T63 + 1),) * 3, 0.0,
                    (3, 4))
    monkeypatch.setattr(json_store, "extract_columns", _extract)
    table = aggregate.ResidentColumns(0, cols, "t", sorted(
        set(X) | set(K), key=lambda c: c.path))
    over = [_req(_sum("s", F0, F1, F2))]
    with pytest.raises(OverflowError, match="64 bits"):
        aggregate.encode(over, table, 16, aggregate.batch_products(over))
    count = [_req(Output("n", "count"))]
    table.n_rows = aggregate.MAX_ROWS
    aggregate.encode(count, table, 16, ())
    table.n_rows = aggregate.MAX_ROWS + 1
    with pytest.raises(OverflowError, match="32 bits"):
        aggregate.encode(count, table, 16, ())
