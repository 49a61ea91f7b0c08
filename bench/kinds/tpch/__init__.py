"""The ``tpch`` kind: TPC-H Q1 and Q6 over ``lineitem`` rows stored as
JSON objects in the paper's store (``lineitem``, ``deploy``), deployed as
a replicated ``ShardedWarren`` and served by ``RetrievalServer``'s
aggregate requests: the scanned columns resident on the chip, the sums
exact.  Every answer is checked against the plain reference
(``reference``), which must equal it exactly.

Requests, all reads, and the mix's sections that make them (drawn from
the mix's ``queries.set_seed``; the run's seed orders them):

- ``q1``: Pricing Summary Report (specification 2.4.1), ``DELTA`` uniform
  over ``q1.delta`` days (inclusive);
- ``q6``: Forecasting Revenue Change (2.4.6), ``DATE`` January 1 of a
  year uniform over ``q6.years``, ``DISCOUNT`` uniform over
  ``q6.discount`` hundredths, ``QUANTITY`` uniform over ``q6.quantity``.
"""

from __future__ import annotations

import datetime as dt
import time

import numpy as np

import drive
import harness

from . import lineitem, reference

REQUESTS = {"q1": "read", "q6": "read"}
# the columns each query reads: rows x 4 bytes x these is its scan
COLUMNS_READ = {"q1": 7, "q6": 4}


class Query:
    """One request: its parameters, as the reference takes them, and the
    program's request."""

    def __init__(self, params: tuple):
        self.params = params
        self.request = (q1(*params[1:]) if params[0] == "q1"
                        else q6(*params[1:]))


def _columns():
    from repro.core.aggregate import Column
    from repro.core.json_store import days_path
    return {"ship": Column(days_path(":l_shipdate:"), 0),
            "qty": Column(":l_quantity:", 0),
            "price": Column(":l_extendedprice:", 2),
            "disc": Column(":l_discount:", 2),
            "tax": Column(":l_tax:", 2),
            "flag": Column(":l_returnflag:", None),
            "status": Column(":l_linestatus:", None)}


def q1(delta: int):
    from repro.core.aggregate import AggregateRequest, Factor, Output, Range
    c = _columns()
    charge = (Factor(c["price"]), Factor(c["disc"], -1, 100),
              Factor(c["tax"], 1, 100))
    return AggregateRequest(
        lineitem.COLLECTION,
        where=(Range(c["ship"],
                     hi=lineitem.day(dt.date(1998, 12, 1)) - delta),),
        group_by=(c["flag"], c["status"]),
        outputs=(Output("sum_qty", "sum", (Factor(c["qty"]),)),
                 Output("sum_base_price", "sum", (Factor(c["price"]),)),
                 Output("sum_disc_price", "sum", charge[:2]),
                 Output("sum_charge", "sum", charge),
                 Output("avg_qty", "avg", (Factor(c["qty"]),)),
                 Output("avg_price", "avg", (Factor(c["price"]),)),
                 Output("avg_disc", "avg", (Factor(c["disc"]),)),
                 Output("count_order", "count")))


def q6(year: int, discount: int, quantity: int):
    from repro.core.aggregate import AggregateRequest, Factor, Output, Range
    c = _columns()
    return AggregateRequest(
        lineitem.COLLECTION,
        where=(Range(c["ship"], lineitem.day(dt.date(year, 1, 1)),
                     lineitem.day(dt.date(year + 1, 1, 1)) - 1),
               Range(c["disc"], discount - 1, discount + 1),
               Range(c["qty"], hi=quantity - 1)),
        outputs=(Output("revenue", "sum",
                        (Factor(c["price"]), Factor(c["disc"]))),))


def payloads(mix: dict, config: dict, counts: dict, stream: int) -> dict:
    out = {}
    for part in REQUESTS:
        rng = np.random.default_rng([mix["queries"]["set_seed"],
                                     list(REQUESTS).index(part) + 1, stream])
        n, p = counts.get(part, 0), mix[part]
        if part == "q1":
            lo, hi = p["delta"]
            params = [("q1", int(d)) for d in rng.integers(lo, hi + 1, n)]
        else:
            params = list(zip(
                ["q6"] * n,
                map(int, rng.integers(p["years"][0], p["years"][1] + 1, n)),
                map(int, rng.integers(p["discount"][0],
                                      p["discount"][1] + 1, n)),
                map(int, rng.integers(p["quantity"][0],
                                      p["quantity"][1] + 1, n))))
        out[part] = [Query(x) for x in params]
    return out


def submit(server, query: Query):
    return server.batcher.submit(query.request)


class Deployment(harness.Deployment):
    """The configuration's ``ShardedWarren`` behind a ``RetrievalServer``;
    read-only."""

    def __init__(self, cell, state_dir):
        from repro.train.serve import BatcherConfig, RetrievalServer

        from . import deploy
        cfg = cell.config
        self.warren, times = deploy.open_deployment(cfg, state_dir)
        srv = cfg["server"]
        server = RetrievalServer(
            self.warren, batcher=BatcherConfig(max_batch=srv["max_batch"],
                                               max_wait_ms=srv["max_wait_ms"]))
        server.timings = _Timings()
        super().__init__(server, times)

    def close(self) -> None:
        self.stop()
        self.warren.close()


def _builds() -> int:
    from repro import obs
    return sum(m.value for _, m in
               obs.registry().series("serve_column_builds_total"))


def _Timings():
    """The server's timings, whose snapshots also count the column tables
    built so far, so a window's pair of them gives its builds."""
    from repro.dist.parallel import ScatterTimings

    class Timings(ScatterTimings):
        def snapshot(self):
            return dict(super().snapshot(), column_builds=_builds())
    return Timings()


def open(cell, state_dir, log_dir, span) -> Deployment:
    # before anything is built: a program without aggregate requests
    # cannot serve this kind
    try:
        from repro.core.aggregate import AggregateRequest  # noqa: F401
        from repro.core.json_store import days_path, json_text  # noqa: F401
    except ImportError as e:
        raise RuntimeError(f"the program serves no aggregate requests: {e}"
                           ) from None
    return Deployment(cell, state_dir)


def warm(session, slack: int) -> str:
    """Serve one request of each kind in the pool, together: every group
    builds its resident columns and the server compiles the scan's
    shapes."""
    first = {}
    for q in session.pool:
        first.setdefault(q.params[0], q)
    handles = [submit(session.server, q) for q in first.values()]
    for h in handles:
        h.get(timeout=drive.GRACE_S)
    return f"resident columns and scans for {sorted(first)}"


def check(cell, session, window, seed: int) -> dict:
    """``unanswered``: reads due in the window never answered (limit 0);
    ``answers_wrong``: answered reads whose answer is not exactly the
    reference's, every group row, sum, average and count, in SQL order
    (limit 0).  The reference runs once per distinct parameter set."""
    t0 = time.perf_counter()
    rows = reference.table(lineitem.generate(cell.config)["columns"])
    reads, pool = window.reads, session.pool
    ok = ~np.isnan(reads.done)
    truth, wrong = {}, 0
    for i in np.flatnonzero(ok):
        params = pool[int(reads.query[i])].params
        if params not in truth:
            truth[params] = reference.answer(rows, params)
        wrong += reads.answers[i] != truth[params]
    harness.log(f"check: {int(ok.sum())} answers against {len(truth)} "
                f"reference answers in {time.perf_counter() - t0:.3f}s")
    return {"unanswered": {"value": int((~ok).sum()), "limit": 0},
            "answers_wrong": {"value": int(wrong), "limit": 0}}


def context(session) -> dict:
    return {"pool": session.pool, "kernel": "agg_scan",
            "n_rows": len(lineitem.generate(session.cell.config)["rows"]),
            "columns_read": COLUMNS_READ}
