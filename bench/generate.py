"""The one traffic generator: a mix file's parameters, the collection and a
seed in, a plan of requests out.  The program sees none of it until a
request arrives.

A mix (``bench/traffic/<name>.json``) holds:

- ``loop``: ``open`` (requests due on a schedule, whatever the server
  does) or ``closed`` (``clients`` callers, each sending its next query
  when the last one returns);
- ``rate_per_s`` (open loop): the mean arrival rate;
- ``pattern`` (open loop, optional): the arrival rate's shape, a list of
  ``[seconds, relative rate]`` steps repeated through the window and
  scaled so that their mean is ``rate_per_s`` (on/off bursts, a diurnal
  ramp); without it the rate is constant;
- ``requests``: the share of each kind of request, ``query`` (a ranked
  query) and ``update`` (a transaction that replaces a passage); default
  all queries;
- ``queries``: how queries are made (``corpus.known_item_queries``):
  ``set_seed``, ``min_terms``, ``max_terms``, and optionally
  ``max_df_share``, which keeps from a target passage only words in at
  most that share of the passages;
- ``update`` (where updates are mixed in): ``key_zipf``, YCSB's Zipfian
  constant over passages, ``key_seed`` for the fixed scramble of
  popularity ranks over passage ids (YCSB's scrambled Zipfian), and
  ``writers``, the client threads that commit updates;
- ``set_size`` (closed loop): the queries the clients share;
- ``warmup_s``: seconds of the mix replayed, with requests of their own,
  after the shapes are compiled and before the window.

Every seed gets the same work in another order.  A window of ``S``
seconds has ``round(rate_per_s * S)`` requests; what they are (the
queries, the passages updated and their new text) and the set of gaps
between arrivals, in time scaled by the rate, are drawn from the mix's
own seeds and the window's stream; the run's seed orders them.  So a seed
changes the batches and the interleaving, not the amount of work.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

import corpus as corpus_mod

KINDS = ("query", "update")


@dataclasses.dataclass
class Update:
    passage: int             # passage id whose text is replaced
    ranks: np.ndarray        # the new version's words


@dataclasses.dataclass
class Schedule:
    """Open loop: request ``i`` is due ``due[i]`` seconds into the window
    and is query ``query[i]`` (an index into ``queries``) or, where that is
    -1, ``updates[i]``."""
    due: np.ndarray
    query: np.ndarray
    updates: List[Optional[Update]]
    queries: List[List[int]]


@dataclasses.dataclass
class ClosedPlan:
    """Closed loop: client ``c`` sends ``queries[order[c::clients]]`` in
    turn, and from the start again when it runs out."""
    clients: int
    order: np.ndarray
    queries: List[List[int]]

    def of(self, client: int) -> np.ndarray:
        return self.order[client::self.clients]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run's ``seed`` (any size)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def _set_rng(mix: dict, part: str, stream: int) -> np.random.Generator:
    """The generator of what a window's requests are: the mix's, not the
    run's."""
    return np.random.default_rng([mix["queries"]["set_seed"],
                                  KINDS.index(part) + 1, stream])


def shares(mix: dict) -> dict:
    req = mix.get("requests", {"query": 1.0})
    unknown = set(req) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown request kinds {sorted(unknown)}; "
                         f"the drivers serve {KINDS}")
    return req


def queries(mix: dict, c: corpus_mod.Corpus, stream: int, n: int,
            df: Optional[np.ndarray] = None) -> List[List[int]]:
    q = mix["queries"]
    cap = q.get("max_df_share")
    if cap is not None:
        df = corpus_mod.document_frequency(c) if df is None else df
        keep = df <= cap * c.n
        rng, out = _set_rng(mix, "query", stream), []
        while len(out) < n:
            for terms in corpus_mod.known_item_queries(c, q, rng, n):
                terms = [t for t in terms if keep[t]]
                if len(terms) >= q["min_terms"] and len(out) < n:
                    out.append(terms)
        return out
    return corpus_mod.known_item_queries(c, q, _set_rng(mix, "query", stream),
                                         n)


def arrival_times(mix: dict, n: int, seconds: float, gaps: np.ndarray
                  ) -> np.ndarray:
    """``n`` arrival times in ``[0, seconds)`` from ``n + 1`` gaps in time
    scaled by the rate (any positive numbers; only their shares count),
    through the inverse of the mix's cumulative rate."""
    cum = np.cumsum(gaps)[:n] / gaps.sum()
    steps = mix.get("pattern")
    if not steps:
        return cum * seconds
    dur = np.array([s for s, _ in steps], np.float64)
    rel = np.array([r for _, r in steps], np.float64)
    reps = int(np.ceil(seconds / dur.sum())) + 1
    edges = np.concatenate([[0.0], np.cumsum(np.tile(dur, reps))])
    mass = np.concatenate([[0.0], np.cumsum(np.tile(dur * rel, reps))])
    total = np.interp(seconds, edges, mass)
    return np.minimum(np.interp(cum * total, mass, edges),
                      np.nextafter(seconds, 0))


def open_schedule(mix: dict, spec: dict, c: corpus_mod.Corpus, seed: int,
                  seconds: float, stream: int = 0) -> Schedule:
    """The open-loop requests due in ``[0, seconds)``."""
    n = int(round(mix["rate_per_s"] * seconds))
    n_upd = int(round(shares(mix).get("update", 0.0) * n))
    n_q = n - n_upd
    qs = queries(mix, c, stream, n_q)
    upd: List[Update] = []
    if n_upd:
        rng = _set_rng(mix, "update", stream)
        order = np.random.default_rng(mix["update"]["key_seed"]).permutation(
            c.n)
        cdf = corpus_mod.zipf_cdf(c.n, mix["update"]["key_zipf"], 0.0)
        keys = order[corpus_mod.draw_ranks(rng, cdf, n_upd) - 1]
        upd = [Update(int(p), corpus_mod.new_version(c, spec, rng))
               for p in keys]
    gaps = _set_rng(mix, "query", 1000 + stream).exponential(size=n + 1)
    rng = rng_for(seed, stream)
    due = arrival_times(mix, n, seconds, rng.permutation(gaps))
    is_upd = np.zeros(n, bool)
    is_upd[rng.choice(n, n_upd, replace=False)] = True
    query = np.full(n, -1, np.int64)
    query[~is_upd] = rng.permutation(n_q)
    updates: List[Optional[Update]] = [None] * n
    for i, u in zip(np.flatnonzero(is_upd), rng.permutation(n_upd)):
        updates[i] = upd[u]
    return Schedule(due, query, updates, qs)


def closed_plan(mix: dict, c: corpus_mod.Corpus, seed: int,
                stream: int = 0) -> ClosedPlan:
    qs = queries(mix, c, stream, mix["set_size"])
    order = rng_for(seed, stream).permutation(len(qs))
    return ClosedPlan(mix["clients"], order, qs)


def plan(mix: dict, spec: dict, c: corpus_mod.Corpus, seed: int,
         seconds: float, stream: int = 0):
    if mix["loop"] == "closed":
        return closed_plan(mix, c, seed, stream)
    return open_schedule(mix, spec, c, seed, seconds, stream)
