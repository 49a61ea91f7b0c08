"""The plain reference: TPC-H Q1 (section 2.4.1) and Q6 (section 2.4.6)
written directly over the generated rows in Python integers, decimals
as fixed-point (section 2.1.3): prices in cents, discounts and taxes in
hundredths, dates as days since 1970-01-01.  Imports nothing of the
program.

An answer is a list of rows in SQL order: Q1's rows ``(returnflag,
linestatus, sum_qty, sum_base_price, sum_disc_price, sum_charge,
avg_qty, avg_price, avg_disc, count_order)`` ordered by the two flags,
sums as exact ``Decimal`` and averages as exact ``Fraction``; Q6's one
row ``(revenue,)``, None over no rows.  With ``float32``, every sum is
accumulated in float32 instead, one row after another: the control that
the check must refuse."""

from __future__ import annotations

import datetime as dt
from decimal import Decimal
from fractions import Fraction
from typing import List, Tuple

import numpy as np

EPOCH = dt.date(1970, 1, 1)


def day(d: dt.date) -> int:
    return (d - EPOCH).days


def table(columns: dict) -> List[Tuple]:
    """The rows as tuples of Python ints and strings: (shipdate,
    returnflag, linestatus, quantity, extendedprice, discount, tax)."""
    keys = ("shipdate", "returnflag", "linestatus", "quantity",
            "extendedprice", "discount", "tax")
    return list(zip(*[columns[k].tolist() for k in keys]))


def _dec(units, scale: int) -> Decimal:
    """``units`` ten-to-the-``scale``ths: exact for an int."""
    if isinstance(units, int):
        return Decimal(f"{units}E-{scale}")
    return Decimal(float(units)).scaleb(-scale)


def _add(acc, x, float32: bool):
    return np.float32(acc + np.float32(x)) if float32 else acc + x


def q1(rows: List[Tuple], delta: int, float32: bool = False) -> list:
    cutoff = day(dt.date(1998, 12, 1)) - delta
    zero = np.float32(0) if float32 else 0
    groups = {}
    for ship, flag, status, qty, price, disc, tax in rows:
        if ship > cutoff:
            continue
        g = groups.get((flag, status))
        if g is None:
            g = groups[flag, status] = [zero] * 5 + [0]
        disc_price = price * (100 - disc)
        g[0] = _add(g[0], qty, float32)
        g[1] = _add(g[1], price, float32)
        g[2] = _add(g[2], disc_price, float32)
        g[3] = _add(g[3], disc_price * (100 + tax), float32)
        g[4] = _add(g[4], disc, float32)
        g[5] += 1
    out = []
    for (flag, status), (qty, base, dprice, charge, disc, n) in sorted(
            groups.items()):
        avg = [Fraction(int(x), s * n) if not float32
               else Fraction(float(x)) / (s * n)
               for x, s in ((qty, 1), (base, 100), (disc, 100))]
        out.append((flag, status, _dec(qty, 0), _dec(base, 2),
                    _dec(dprice, 4), _dec(charge, 6), *avg, n))
    return out


def q6(rows: List[Tuple], year: int, discount: int, quantity: int,
       float32: bool = False) -> list:
    """``discount`` in hundredths."""
    lo, hi = day(dt.date(year, 1, 1)), day(dt.date(year + 1, 1, 1))
    revenue, n = (np.float32(0) if float32 else 0), 0
    for ship, _, _, qty, price, disc, _ in rows:
        if (lo <= ship < hi and discount - 1 <= disc <= discount + 1
                and qty < quantity):
            revenue = _add(revenue, price * disc, float32)
            n += 1
    return [(_dec(revenue, 4) if n else None,)]


def answer(rows: List[Tuple], params: tuple, float32: bool = False) -> list:
    """The answer to ``("q1", delta)`` or ``("q6", year, discount,
    quantity)``."""
    if params[0] == "q1":
        return q1(rows, *params[1:], float32=float32)
    return q6(rows, *params[1:], float32=float32)
