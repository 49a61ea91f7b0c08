"""TPC-H ``LINEITEM`` rows from a seed, after the specification's data
generation rules (TPC Benchmark H, revision 3, section 4.2.3), at the
configuration's scale factor.

Per order: ``O_ORDERDATE`` uniform over [1992-01-01, 1998-12-31 - 151
days], 1-7 lines, sparse order keys (the first 8 of every 32).  Per line:
``L_PARTKEY`` uniform over SF x 200,000 parts, ``L_SUPPKEY`` from the
part and one of its 4 suppliers, ``L_QUANTITY`` 1-50, ``L_EXTENDEDPRICE``
= quantity x ``P_RETAILPRICE`` (the part's formula), ``L_DISCOUNT``
0.00-0.10, ``L_TAX`` 0.00-0.08, ship date = order date + 1-121 days,
commit date = order date + 30-90, receipt date = ship date + 1-30,
``L_RETURNFLAG`` R or A where received by CURRENTDATE (1995-06-17) else
N, ``L_LINESTATUS`` O where shipped after it else F, ship instructions
and modes from the specification's lists, and a comment of 10-43
characters from a word pool (``assumed`` in the configuration).

``columns`` holds the numbers as exact integers (cents, hundredths, days
since 1970-01-01) for the reference; ``rows`` the JSON objects stored,
dates as ISO strings."""

from __future__ import annotations

import datetime as dt
import functools
import json

import numpy as np

START = dt.date(1992, 1, 1)
CURRENT = dt.date(1995, 6, 17)
END = dt.date(1998, 12, 31)
EPOCH = dt.date(1970, 1, 1)
INSTRUCTIONS = ("DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN")
MODES = ("REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB")
# nouns, verbs, adjectives and adverbs of the specification's text grammar
# (section 4.2.2.10)
WORDS = ("foxes ideas theodolites pinto beans instructions dependencies "
         "excuses platelets asymptotes courts dolphins multipliers "
         "sauternes warthogs frets dinos attainments somas Tiresias "
         "patterns forges braids hockey players frays warhorses dugouts "
         "notornis epitaphs pearls tithes waters orbits gifts sheaves "
         "depths sentiments decoys realms pains grouches escapades "
         "packages requests accounts deposits sleep wake are cajole haggle "
         "nag use boost affix detect integrate maintain nod was lose sublate "
         "solve thrash promise engage hinder print x-ray breach eat grow "
         "impress mold poach serve run dazzle snooze doze unwind kindle play "
         "hang believe doubt furious sly careful blithe quick fluffy slow "
         "quiet ruthless thin close dogged daring brave stealthy permanent "
         "enticing idle busy regular final ironic even bold silent "
         "furiously sometimes carefully quickly slyly blithely").split()
KEYS = ("scale_factor", "data_seed")
COLLECTION = "lineitem"
DATE_PATHS = (":l_shipdate:", ":l_commitdate:", ":l_receiptdate:")


def day(d: dt.date) -> int:
    return (d - EPOCH).days


def _iso(days: np.ndarray) -> list:
    base = EPOCH.toordinal()
    return [dt.date.fromordinal(base + int(x)).isoformat() for x in days]


def _comments(rng: np.random.Generator, n: int) -> list:
    lens = rng.integers(10, 44, n)
    picks = rng.integers(0, len(WORDS), (n, 12))
    out = []
    for i in range(n):
        text = ""
        for j in picks[i]:
            text = f"{text} {WORDS[j]}" if text else WORDS[j]
            if len(text) >= lens[i]:
                break
        out.append(text[:lens[i]].strip())
    return out


@functools.lru_cache(maxsize=2)
def _generate(key: str) -> dict:
    cfg = json.loads(key)
    sf = cfg["scale_factor"]
    rng = np.random.default_rng(cfg["data_seed"])
    n_orders = int(round(sf * 1_500_000))
    parts, supps = int(round(sf * 200_000)), int(round(sf * 10_000))
    odate = day(START) + rng.integers(
        0, (END - dt.timedelta(days=151) - START).days + 1, n_orders)
    lines = rng.integers(1, 8, n_orders)
    order = np.repeat(np.arange(n_orders), lines)
    n = len(order)
    c = {}
    c["orderkey"] = (order // 8) * 32 + order % 8 + 1
    first = np.repeat(np.cumsum(lines) - lines, lines)
    c["linenumber"] = np.arange(n) - first + 1
    c["partkey"] = rng.integers(1, parts + 1, n)
    c["suppkey"] = (c["partkey"] + rng.integers(0, 4, n)
                    * (supps // 4 + (c["partkey"] - 1) // supps)) % supps + 1
    c["quantity"] = rng.integers(1, 51, n)
    retail = (90000 + (c["partkey"] // 10) % 20001
              + 100 * (c["partkey"] % 1000))            # cents
    c["extendedprice"] = c["quantity"] * retail         # cents
    c["discount"] = rng.integers(0, 11, n)              # hundredths
    c["tax"] = rng.integers(0, 9, n)                    # hundredths
    c["shipdate"] = odate[order] + rng.integers(1, 122, n)
    c["commitdate"] = odate[order] + rng.integers(30, 91, n)
    c["receiptdate"] = c["shipdate"] + rng.integers(1, 31, n)
    ra = np.where(rng.integers(0, 2, n) == 0, "R", "A")
    c["returnflag"] = np.where(c["receiptdate"] <= day(CURRENT), ra, "N")
    c["linestatus"] = np.where(c["shipdate"] > day(CURRENT), "O", "F")
    instruct = rng.integers(0, len(INSTRUCTIONS), n)
    mode = rng.integers(0, len(MODES), n)
    comment = _comments(rng, n)
    dates = {k: _iso(c[k]) for k in ("shipdate", "commitdate",
                                     "receiptdate")}
    rows = []
    for i in range(n):
        rows.append({
            "l_orderkey": int(c["orderkey"][i]),
            "l_partkey": int(c["partkey"][i]),
            "l_suppkey": int(c["suppkey"][i]),
            "l_linenumber": int(c["linenumber"][i]),
            "l_quantity": int(c["quantity"][i]),
            "l_extendedprice": int(c["extendedprice"][i]) / 100,
            "l_discount": int(c["discount"][i]) / 100,
            "l_tax": int(c["tax"][i]) / 100,
            "l_returnflag": str(c["returnflag"][i]),
            "l_linestatus": str(c["linestatus"][i]),
            "l_shipdate": dates["shipdate"][i],
            "l_commitdate": dates["commitdate"][i],
            "l_receiptdate": dates["receiptdate"][i],
            "l_shipinstruct": INSTRUCTIONS[instruct[i]],
            "l_shipmode": MODES[mode[i]],
            "l_comment": comment[i],
        })
    return {"columns": c, "rows": rows}


def generate(config: dict) -> dict:
    """The configuration's rows and columns, made once per process."""
    return _generate(json.dumps({k: config[k] for k in KEYS},
                                sort_keys=True))
