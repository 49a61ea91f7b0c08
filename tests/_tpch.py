"""TPC-H ``lineitem`` rows, Q1 and Q6 as aggregate requests, and their
plain reference, for the aggregate tests.

Rows follow the specification's generation rules (section 4.2.3) at a
small scale, drawn from a seed with ``random``; each is a dict of the 16
columns with dates as ISO strings, as it is stored with ``add_json``.  The
reference is Q1 (section 2.4.1) and Q6 (section 2.4.6) written directly
over the rows in Python integers, decimals as fixed-point (section 2.1.3).
"""

import datetime as dt
import random
from decimal import Decimal
from fractions import Fraction

from repro.core import json_store
from repro.core.aggregate import (AggregateRequest, Column, Factor, Output,
                                  Range)

START, CURRENT, END = dt.date(1992, 1, 1), dt.date(1995, 6, 17), \
    dt.date(1998, 12, 31)
DATES = (":l_shipdate:", ":l_commitdate:", ":l_receiptdate:")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
WORDS = ("furiously", "slyly", "carefully", "blithely", "quickly",
         "deposits", "packages", "requests", "accounts", "ideas", "sleep",
         "haggle", "nag", "final", "regular", "ironic", "pending", "even")


def lineitems(orders: int, seed: int, parts: int = 2000):
    rng = random.Random(seed)
    span = (END - dt.timedelta(days=151) - START).days
    rows = []
    for o in range(orders):
        odate = START + dt.timedelta(days=rng.randint(0, span))
        for line in range(1, rng.randint(1, 7) + 1):
            part = rng.randint(1, parts)
            qty = rng.randint(1, 50)
            price = 90000 + (part // 10) % 20001 + 100 * (part % 1000)
            ship = odate + dt.timedelta(days=rng.randint(1, 121))
            receipt = ship + dt.timedelta(days=rng.randint(1, 30))
            rows.append({
                "l_orderkey": (o // 8) * 32 + o % 8 + 1,
                "l_partkey": part, "l_suppkey": rng.randint(1, 100),
                "l_linenumber": line, "l_quantity": qty,
                "l_extendedprice": qty * price / 100,
                "l_discount": rng.randint(0, 10) / 100,
                "l_tax": rng.randint(0, 8) / 100,
                "l_returnflag": (rng.choice("RA") if receipt <= CURRENT
                                 else "N"),
                "l_linestatus": "O" if ship > CURRENT else "F",
                "l_shipdate": ship.isoformat(),
                "l_commitdate": (odate + dt.timedelta(
                    days=rng.randint(30, 90))).isoformat(),
                "l_receiptdate": receipt.isoformat(),
                "l_shipinstruct": rng.choice(INSTRUCTIONS),
                "l_shipmode": rng.choice(MODES),
                "l_comment": " ".join(rng.choice(WORDS)
                                      for _ in range(rng.randint(2, 5))),
            })
    return rows


def ingest(warren, rows):
    """Store ``rows`` in one transaction, then give their dates day
    numbers in a second (post-hoc annotation)."""
    with warren:
        warren.transaction()
        for r in rows:
            json_store.add_json(warren, r, collection="lineitem")
        warren.commit()
    with warren:
        warren.transaction()
        json_store.annotate_dates(warren, DATES)
        warren.commit()


SHIP = Column(json_store.days_path(":l_shipdate:"), 0)
QTY, PRICE = Column(":l_quantity:", 0), Column(":l_extendedprice:", 2)
DISC, TAX = Column(":l_discount:", 2), Column(":l_tax:", 2)
FLAG, STATUS = Column(":l_returnflag:", None), Column(":l_linestatus:", None)


def _day(d: dt.date) -> int:
    return json_store.day_number(d.year, d.month, d.day)


def q1(delta: int) -> AggregateRequest:
    charge = (Factor(PRICE), Factor(DISC, -1, 100), Factor(TAX, 1, 100))
    return AggregateRequest(
        "lineitem",
        where=(Range(SHIP, hi=_day(dt.date(1998, 12, 1)) - delta),),
        group_by=(FLAG, STATUS),
        outputs=(Output("sum_qty", "sum", (Factor(QTY),)),
                 Output("sum_base_price", "sum", (Factor(PRICE),)),
                 Output("sum_disc_price", "sum", charge[:2]),
                 Output("sum_charge", "sum", charge),
                 Output("avg_qty", "avg", (Factor(QTY),)),
                 Output("avg_price", "avg", (Factor(PRICE),)),
                 Output("avg_disc", "avg", (Factor(DISC),)),
                 Output("count_order", "count")))


def q6(year: int, discount: int, quantity: int) -> AggregateRequest:
    """``discount`` in hundredths."""
    lo = _day(dt.date(year, 1, 1))
    return AggregateRequest(
        "lineitem",
        where=(Range(SHIP, lo, _day(dt.date(year + 1, 1, 1)) - 1),
               Range(DISC, discount - 1, discount + 1),
               Range(QTY, hi=quantity - 1)),
        outputs=(Output("revenue", "sum", (Factor(PRICE), Factor(DISC))),))


# -- the plain reference ---------------------------------------------------- #
def _cents(x: float) -> int:
    return round(x * 100)


def ref_q1(rows, delta):
    cutoff = dt.date(1998, 12, 1) - dt.timedelta(days=delta)
    groups = {}
    for r in rows:
        if dt.date.fromisoformat(r["l_shipdate"]) > cutoff:
            continue
        price, disc, tax = (_cents(r["l_extendedprice"]),
                            _cents(r["l_discount"]), _cents(r["l_tax"]))
        g = groups.setdefault((r["l_returnflag"], r["l_linestatus"]),
                              [0, 0, 0, 0, 0, 0])
        g[0] += r["l_quantity"]
        g[1] += price
        g[2] += price * (100 - disc)
        g[3] += price * (100 - disc) * (100 + tax)
        g[4] += disc
        g[5] += 1
    return [(f, s, Decimal(qty), Decimal(base) / 100,
             Decimal(dprice) / 10 ** 4, Decimal(charge) / 10 ** 6,
             Fraction(qty, n), Fraction(base, 100 * n),
             Fraction(disc, 100 * n), n)
            for (f, s), (qty, base, dprice, charge, disc, n)
            in sorted(groups.items())]


def ref_q6(rows, year, discount, quantity):
    revenue, n = 0, 0
    for r in rows:
        ship = dt.date.fromisoformat(r["l_shipdate"])
        disc = _cents(r["l_discount"])
        if (dt.date(year, 1, 1) <= ship < dt.date(year + 1, 1, 1)
                and discount - 1 <= disc <= discount + 1
                and r["l_quantity"] < quantity):
            revenue += _cents(r["l_extendedprice"]) * disc
            n += 1
    return [(Decimal(revenue) / 10 ** 4 if n else None,)]
