"""The ``tpch`` kind: its reference against a table computed by hand, its
plans pinned, a run rehearsed on the CPU at a tiny scale factor, and its
check catching planted faults."""

import datetime as dt
import hashlib
import json
from decimal import Decimal
from fractions import Fraction

import numpy as np
import pytest

import cells
import generate
import harness
from conftest import ROOT
from kinds.tpch import lineitem, reference

CELL = "json.tpch-q1q6"
TINY_SF = 0.0005          # 750 orders, about 3,000 rows


def _day(y, m, d):
    return reference.day(dt.date(y, m, d))


# (shipdate, returnflag, linestatus, quantity, cents, discount, tax)
HAND = [
    (_day(1994, 3, 1), "A", "F", 10, 100000, 5, 2),
    (_day(1994, 7, 1), "A", "F", 20, 300000, 10, 8),
    (_day(1998, 9, 1), "N", "O", 30, 50000, 0, 0),    # in at 90, out at 120
    (_day(1995, 2, 1), "R", "F", 5, 7000, 6, 1),
]


def test_reference_q1_against_a_table_computed_by_hand():
    got = reference.q1(HAND, 90)          # cutoff 1998-09-02
    disc_a = 100000 * 95 + 300000 * 90
    charge_a = 100000 * 95 * 102 + 300000 * 90 * 108
    assert got == [
        ("A", "F", Decimal(30), Decimal("4000.00"),
         Decimal(disc_a) / 10 ** 4, Decimal(charge_a) / 10 ** 6,
         Fraction(15), Fraction(2000), Fraction(15, 200), 2),
        ("N", "O", Decimal(30), Decimal("500.00"), Decimal("500.0000"),
         Decimal("500.000000"), Fraction(30), Fraction(500), Fraction(0), 1),
        ("R", "F", Decimal(5), Decimal("70.00"), Decimal("65.8000"),
         Decimal("66.458000"), Fraction(5), Fraction(70), Fraction(6, 100),
         1),
    ]
    # a cutoff before 1998-09-01 leaves the N/O row out
    assert [r[:2] for r in reference.q1(HAND, 120)] == [("A", "F"),
                                                         ("R", "F")]


def test_reference_q6_against_a_table_computed_by_hand():
    # 1994, discount 0.05-0.07, quantity < 24: the first row only
    assert reference.q6(HAND, 1994, 6, 24) == [(Decimal("50.0000"),)]
    # 1995, 0.05-0.07: the R/F row
    assert reference.q6(HAND, 1995, 6, 24) == [(Decimal("4.2000"),)]
    assert reference.q6(HAND, 1993, 6, 24) == [(None,)]


def test_the_float32_control_is_not_the_reference():
    cols = lineitem.generate({"scale_factor": 0.002, "data_seed": 1992})[
        "columns"]
    rows = reference.table(cols)
    assert reference.answer(rows, ("q1", 90)) != \
        reference.answer(rows, ("q1", 90), float32=True)


def _fingerprint(s) -> str:
    h = hashlib.sha256()
    h.update(np.asarray(s.due, np.float64).tobytes())
    for q in s.query:
        h.update(repr(s.queries[q].params).encode())
    return h.hexdigest()


PLANS = {
    (11, harness.WARM_STREAM):
        "b8542f3159f6bd97ed8d0ae3c7728398492b9e4aa5494a2a557acb0bdb4de257",
    (11, harness.WINDOW_STREAM):
        "2a719459ab39b6d0d508e8e5563d496f2c2489dcfc7cc200f563285ec76170d5",
    (12, harness.WARM_STREAM):
        "81d240462a32de5490fbb22ca3a53ed6e931ba7d63c9bc8b860db0fd4dbb1b07",
    (12, harness.WINDOW_STREAM):
        "ecc62337f170a1e427817c55a8b24508c412844a0d76bcb8e9d6b5a2c88321d2",
}


def test_plans_are_pinned():
    """The traffic of seeds 11 and 12, warm-up and window, at the cell's
    rate: every request's due time and parameters."""
    c = cells.load(CELL, ROOT)
    kind = cells.kind(c.config["kind"], ROOT)
    seconds = {harness.WARM_STREAM: c.mix["warmup_s"],
               harness.WINDOW_STREAM: cells.benchmark(ROOT)["run_seconds"]}
    got = {}
    for seed in (11, 12):
        for stream, s in seconds.items():
            plan = generate.plan(c.mix, kind, c.config, seed, s, stream)
            assert len(plan.due) == round(c.mix["rate_per_s"] * s)
            got[seed, stream] = _fingerprint(plan)
    assert got == PLANS


def test_parameters_cover_the_specifications_ranges():
    c = cells.load(CELL, ROOT)
    kind = cells.kind(c.config["kind"], ROOT)
    load = kind.payloads(c.mix, c.config, {"q1": 2000, "q6": 2000}, 0)
    assert {q.params[1] for q in load["q1"]} == set(range(60, 121))
    assert {q.params[1:] for q in load["q6"]} == {
        (y, d, n) for y in range(1993, 1998) for d in range(2, 10)
        for n in (24, 25)}


@pytest.fixture
def tpch_root(cpu_state):
    """The tiny root with the configuration cut to ``TINY_SF``."""
    bench = json.loads((cpu_state / "BENCHMARK.json").read_text())
    conf = next(c for c in bench["configs"]
                if c["name"] == "tpch-lineitem-sf0.02-4x2")
    path = cpu_state / conf["file"]
    cfg = json.loads(path.read_text())
    cfg["scale_factor"] = TINY_SF
    path.write_text(json.dumps(cfg))
    return cpu_state


def _run(root, seed=2**31 + 11, **kw):
    return harness.run(CELL, seed, 2.0, False, platform="cpu", root=root,
                       **kw)


def test_a_run_rehearsed_on_the_cpu(tpch_root):
    out = _run(tpch_root)
    assert out["correct"] and out["failed"] == 0
    assert out["checks"] == {"unanswered": {"value": 0, "limit": 0},
                             "answers_wrong": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"query_p50_ms", "setup_s"}
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


def test_check_catches_sums_carried_in_float32(tpch_root, monkeypatch):
    from repro.core import aggregate
    fetch = aggregate.fetch

    def float32(results):
        return [r.astype(np.float32).astype(np.int64)
                for r in fetch(results)]
    monkeypatch.setattr(aggregate, "fetch", float32)
    out = _run(tpch_root)
    assert not out["correct"]
    assert out["checks"]["answers_wrong"]["value"] > 0


def test_check_catches_a_row_dropped_from_a_groups_columns(tpch_root,
                                                           monkeypatch):
    from repro.core import json_store
    from repro.dist.shard_router import STRIPE
    extract = json_store.extract_columns

    def drop_first_of_group_0(w, collection, paths):
        got = extract(w, collection, paths)
        if len(got.rows) and got.rows[0, 0] < STRIPE:
            got.rows = got.rows[1:]
            got.values = {p: v[1:] for p, v in got.values.items()}
            got.present = {p: v[1:] for p, v in got.present.items()}
        return got
    monkeypatch.setattr(json_store, "extract_columns", drop_first_of_group_0)
    out = _run(tpch_root)
    assert not out["correct"]
    assert out["checks"]["answers_wrong"]["value"] > 0


EXTRA = {"l_orderkey": 7, "l_partkey": 1, "l_suppkey": 1, "l_linenumber": 1,
         "l_quantity": 1, "l_extendedprice": 901.0, "l_discount": 0.05,
         "l_tax": 0.01, "l_returnflag": "A", "l_linestatus": "F",
         "l_shipdate": "1994-06-01", "l_commitdate": "1994-05-01",
         "l_receiptdate": "1994-06-05", "l_shipinstruct": "NONE",
         "l_shipmode": "AIR", "l_comment": "a row the window must not see"}


def _insert_then_erase(root, stale: bool, monkeypatch) -> dict:
    """A session whose warm-up serves one extra row, erased by a commit
    before the window; with ``stale``, snapshot keys never move."""
    from repro.core import json_store
    from repro.core.warren import Warren
    if stale:
        monkeypatch.setattr(Warren, "max_seqnum", lambda self: 0)
    s = harness.Session(CELL, platform="cpu", root=root)
    try:
        warren = s.dep.warren
        with warren:
            warren.transaction()
            lo, hi = json_store.add_json(warren, EXTRA,
                                         collection=lineitem.COLLECTION)
            remap = warren.commit()
        with warren:
            warren.transaction()
            json_store.annotate_dates(warren, lineitem.DATE_PATHS)
            warren.commit()
        warm = s.plan(11, s.cell.mix["warmup_s"], harness.WARM_STREAM)
        plan = s.plan(11, 2.0, harness.WINDOW_STREAM)
        s.warm(warm, 0)
        with warren:
            warren.transaction()
            warren.erase(remap(lo), remap(hi))
            warren.commit()
        win = s.window(plan, 2.0)
        s.stop_serving()
        return s.check(win, 11)
    finally:
        s.close()


def test_columns_rebuilt_after_a_commit_pass_the_check(tpch_root,
                                                       monkeypatch):
    checks = _insert_then_erase(tpch_root, False, monkeypatch)
    assert checks["answers_wrong"]["value"] == 0
    assert checks["unanswered"]["value"] == 0


def test_check_catches_columns_not_rebuilt_after_a_commit(tpch_root,
                                                          monkeypatch):
    checks = _insert_then_erase(tpch_root, True, monkeypatch)
    assert checks["answers_wrong"]["value"] > 0
