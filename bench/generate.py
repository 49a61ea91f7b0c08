"""The one traffic generator: a mix file's parameters, the cell's
deployment kind and a seed in, a plan of requests out.  The program sees
none of it until a request arrives.

A mix (``bench/traffic/<name>.json``) holds:

- ``loop``: ``open`` (requests due on a schedule, whatever the server
  does) or ``closed`` (``clients`` callers, each sending its next read
  when the last one returns);
- ``rate_per_s`` (open loop): the mean arrival rate;
- ``pattern`` (open loop, optional): the arrival rate's shape, a list of
  ``[seconds, relative rate]`` steps repeated through the window and
  scaled so that their mean is ``rate_per_s`` (on/off bursts, a diurnal
  ramp); without it the rate is constant;
- ``requests``: the share of each request kind, among those the
  deployment kind serves (its ``REQUESTS``, each a read or a write);
  default all of its first;
- ``queries``: ``set_seed``, the seed of what a window's requests are;
  its other keys, and any further section, are the deployment kind's
  (its ``payloads``);
- ``set_size`` (closed loop): the reads the clients share;
- ``warmup_s``: seconds of the mix replayed, with requests of their own,
  after the shapes are compiled and before the window.

Every seed gets the same work in another order.  A window of ``S``
seconds has ``round(rate_per_s * S)`` requests; what they are (the kind's
payloads) and the set of gaps between arrivals, in time scaled by the
rate, are drawn from the mix's own seeds and the window's stream; the
run's seed orders them.  So a seed changes the batches and the
interleaving, not the amount of work.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Schedule:
    """Open loop: request ``i`` is due ``due[i]`` seconds into the window
    and is read ``query[i]`` (an index into ``queries``) or, where that is
    -1, the write ``updates[i]``."""
    due: np.ndarray
    query: np.ndarray
    updates: List[Optional[Any]]
    queries: List[Any]


@dataclasses.dataclass
class ClosedPlan:
    """Closed loop: client ``c`` sends ``queries[order[c::clients]]`` in
    turn, and from the start again when it runs out."""
    clients: int
    order: np.ndarray
    queries: List[Any]

    def of(self, client: int) -> np.ndarray:
        return self.order[client::self.clients]


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run's ``seed`` (any size)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def shares(mix: dict, kind) -> dict:
    """The mix's share of each request kind; refuses one that ``kind``
    does not serve."""
    req = mix.get("requests", {next(iter(kind.REQUESTS)): 1.0})
    unknown = set(req) - set(kind.REQUESTS)
    if unknown:
        raise ValueError(f"unknown request kinds {sorted(unknown)}; "
                         f"the kind {kind.__name__.rsplit('.', 1)[-1]} serves "
                         f"{list(kind.REQUESTS)}")
    return req


def counts(mix: dict, kind, n: int) -> Dict[str, int]:
    """``n`` requests dealt out by the mix's shares; the kind's first
    request kind takes what rounding leaves."""
    req = shares(mix, kind)
    first, *rest = kind.REQUESTS
    out = {k: int(round(req.get(k, 0.0) * n)) for k in rest}
    return {first: n - sum(out.values()), **out}


def _reads(kind, loads: dict) -> Tuple[List[Any], Dict[str, int]]:
    """Every read payload in one list, in the kind's order, and where each
    read kind's start in it."""
    out, off = [], {}
    for k, rw in kind.REQUESTS.items():
        if rw == "read":
            off[k] = len(out)
            out.extend(loads[k])
    return out, off


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


def open_schedule(mix: dict, kind, config: dict, seed: int, seconds: float,
                  stream: int = 0) -> Schedule:
    """The open-loop requests due in ``[0, seconds)``."""
    n = int(round(mix["rate_per_s"] * seconds))
    count = counts(mix, kind, n)
    loads = kind.payloads(mix, config, count, stream)
    # the gaps' generator: the mix's, apart from every payload's
    gaps = np.random.default_rng([mix["queries"]["set_seed"], 1,
                                  1000 + stream]).exponential(size=n + 1)
    rng = rng_for(seed, stream)
    due = arrival_times(mix, n, seconds, rng.permutation(gaps))
    names = list(kind.REQUESTS)
    of = np.zeros(n, np.int64)
    for i, k in enumerate(names[1:], 1):
        free = np.flatnonzero(of == 0)
        of[free[rng.choice(len(free), count[k], replace=False)]] = i
    reads, off = _reads(kind, loads)
    query = np.full(n, -1, np.int64)
    updates: List[Optional[Any]] = [None] * n
    for i, k in enumerate(names):
        at = np.flatnonzero(of == i)
        idx = rng.permutation(count[k])
        if k in off:
            query[at] = idx + off[k]
        else:
            for j, u in zip(at, idx):
                updates[j] = loads[k][u]
    return Schedule(due, query, updates, reads)


def closed_plan(mix: dict, kind, config: dict, seed: int,
                stream: int = 0) -> ClosedPlan:
    count = counts(mix, kind, mix["set_size"])
    if any(count[k] for k, rw in kind.REQUESTS.items() if rw != "read"):
        raise ValueError("a closed loop sends reads only")
    reads, _ = _reads(kind, kind.payloads(mix, config, count, stream))
    order = rng_for(seed, stream).permutation(len(reads))
    return ClosedPlan(mix["clients"], order, reads)


def plan(mix: dict, kind, config: dict, seed: int, seconds: float,
         stream: int = 0):
    if mix["loop"] == "closed":
        return closed_plan(mix, kind, config, seed, stream)
    return open_schedule(mix, kind, config, seed, seconds, stream)
