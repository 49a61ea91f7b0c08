"""The ``passages`` kind: ranked retrieval over a collection shaped like
MS MARCO passages (``corpus``), deployed as a replicated
``ShardedWarren`` (``deploy``) and served by ``RetrievalServer``, exact
BM25 top-k scored on the chip.  Every answer is judged against the plain
reference (``judge``, ``reference``).

Requests, and the mix's sections that make them:

- ``query`` (a read): a simulated known-item query
  (``corpus.known_item_queries``), made by ``queries``: ``set_seed``,
  ``min_terms``, ``max_terms``, and optionally ``max_df_share``, which
  keeps from a target passage only words in at most that share of the
  passages;
- ``update`` (a write): a transaction that replaces a passage (erase its
  version, append a new one; ``write``), made by ``update``:
  ``key_zipf``, YCSB's Zipfian constant over passages, ``key_seed`` for
  the fixed scramble of popularity ranks over passage ids (YCSB's
  scrambled Zipfian), and ``writers``, the client threads that commit
  updates.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

import drive
import harness

from . import corpus as corpus_mod
from . import judge, write

REQUESTS = {"query": "read", "update": "write"}
COMPILE_THREADS = 8


class Query(list):
    """A query's word ids, and the text the server is sent."""

    def __init__(self, terms):
        super().__init__(terms)
        self.text = corpus_mod.query_text(terms)


@dataclasses.dataclass
class Update:
    passage: int             # passage id whose text is replaced
    ranks: np.ndarray        # the new version's words


def corpus_of(config: dict) -> corpus_mod.Corpus:
    """The configuration's collection, made once per process."""
    return _corpus(json.dumps({k: config[k] for k in corpus_mod.CORPUS_KEYS},
                              sort_keys=True))


@functools.lru_cache(maxsize=2)
def _corpus(key: str) -> corpus_mod.Corpus:
    return corpus_mod.make_corpus(json.loads(key))


def _set_rng(mix: dict, part: str, stream: int) -> np.random.Generator:
    """The generator of what a window's requests of kind ``part`` are: the
    mix's, not the run's."""
    return np.random.default_rng([mix["queries"]["set_seed"],
                                  list(REQUESTS).index(part) + 1, stream])


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


def payloads(mix: dict, config: dict, counts: dict, stream: int) -> dict:
    c = corpus_of(config)
    out = {"query": [Query(q) for q in queries(mix, c, stream,
                                                counts["query"])],
           "update": []}
    n_upd = counts.get("update", 0)
    if n_upd:
        rng = _set_rng(mix, "update", stream)
        order = np.random.default_rng(mix["update"]["key_seed"]).permutation(
            c.n)
        cdf = corpus_mod.zipf_cdf(c.n, mix["update"]["key_zipf"], 0.0)
        keys = order[corpus_mod.draw_ranks(rng, cdf, n_upd) - 1]
        out["update"] = [Update(int(p), corpus_mod.new_version(c, config, rng))
                         for p in keys]
    return out


def submit(server, query: Query):
    return server.batcher.submit(query.text)


class Deployment(harness.Deployment):
    """The configuration's ``ShardedWarren`` behind a ``RetrievalServer``,
    and, where the mix has updates, its writers."""

    def __init__(self, cell, state_dir, log_dir, span):
        from repro.train.serve import BatcherConfig, RetrievalServer

        from . import deploy
        cfg, mix = cell.config, cell.mix
        srv = cfg["server"]
        self.corpus = corpus_of(cfg)
        self.log_dir = log_dir if cfg["deployment"]["durable_log"] else None
        self.warren, self.addrs, times = deploy.open_deployment(
            cfg, self.corpus, state_dir, self.log_dir)
        super().__init__(RetrievalServer(
            self.warren, k=srv["k"], max_terms=srv["max_terms"],
            max_postings=srv["max_postings"],
            batcher=BatcherConfig(max_batch=srv["max_batch"],
                                  max_wait_ms=srv["max_wait_ms"])), times)
        self.writers: List[write.Writer] = []
        if mix.get("requests", {}).get("update", 0.0) > 0:
            def text_of(ranks):
                return " ".join(corpus_mod.word(int(r)) for r in ranks)
            versions, lock = write.Versions(self.addrs), threading.Lock()
            self.writers = [write.Writer(self.warren.clone(), versions,
                                         self.writes, lock, text_of, span)
                            for _ in range(mix["update"]["writers"])]
            write.watch_publish(self.warren, self.writers)
            for w in self.writers:
                w.start()

    def write(self, at: float, upd: Update) -> None:
        self.writers[upd.passage % len(self.writers)].q.put((at, upd))

    def drain(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        while any(w.q.unfinished_tasks for w in self.writers):
            if time.perf_counter() > end:
                return False
            time.sleep(0.01)
        return True

    @property
    def errors(self) -> List[BaseException]:
        return [e for w in self.writers for e in w.errors]

    def stop(self) -> None:
        for w in self.writers:
            w.q.put(None)
        for w in self.writers:
            w.join(timeout=drive.GRACE_S)
        self.writers = []
        self.server.close()

    def close(self) -> None:
        self.stop()
        self.warren.close()


def open(cell, state_dir, log_dir, span) -> Deployment:
    return Deployment(cell, state_dir, log_dir, span)


def device_shapes(server, warren, pool: List[List[int]], slack: int) -> set:
    """Every ``(qp, tp, l, nb)`` block shape the server can score for
    batches drawn from ``pool``, using the server's own bucketing, when
    no posting list or group grows or shrinks by more than ``slack``
    documents."""
    from repro.core import ranking
    feats = [[ranking.TF_PREFIX + ranking.porter_stem(corpus_mod.word(r))
              for r in q] for q in pool]
    uniq = sorted({f for q in feats for f in q})
    with warren:
        per_group = warren.map_groups(lambda w: (
            len(w.annotations(ranking.DOC_FEATURE)),
            [len(w.annotations(f)) for f in uniq]))
    max_batch = server.batcher.cfg.max_batch
    qps = {server._pad_sizes(n, 1, 1)[0] for n in range(1, max_batch + 1)}
    tps = {server._pad_sizes(1, len(q[:server.max_terms]), 1)[1]
           for q in feats}
    shapes = set()
    for n_g, dfs in per_group:
        df = dict(zip(uniq, dfs))
        ls = set()
        for q in feats:
            longest = max(df[f] for f in q)
            if longest == 0 and not slack:
                continue
            for d in range(max(1, longest - slack), longest + slack + 1, 64):
                ls.add(server._pad_sizes(1, 1, d)[2])
            ls.add(server._pad_sizes(1, 1, longest + slack)[2])
        nbs = {server._acc_pad(n)
               for n in range(max(0, n_g - slack), n_g + slack + 1)}
        for qp in qps:
            for tp in tps:
                for l in ls:
                    for nb in nbs:
                        shapes.add((qp, tp, l, nb))
    return shapes


def compile_shapes(shapes: set, k: int) -> None:
    """Compile the served scorer for every shape, several at once, then
    run each once so the window finds all of them ready."""
    import jax
    import jax.numpy as jnp
    from repro.train import serve

    def one(shape):
        qp, tp, l, nb = shape
        serve.bm25_topk.lower(
            jax.ShapeDtypeStruct((qp, tp, l), jnp.int32),
            jax.ShapeDtypeStruct((qp, tp, l), jnp.float32),
            jax.ShapeDtypeStruct((qp, tp), jnp.float32),
            n_docs=nb, k=k).compile()

    with ThreadPoolExecutor(COMPILE_THREADS) as ex:
        list(ex.map(one, sorted(shapes)))
    out = None
    for qp, tp, l, nb in sorted(shapes):
        out = serve.bm25_topk(
            jnp.asarray(np.full((qp, tp, l), nb, np.int32)),
            jnp.asarray(np.zeros((qp, tp, l), np.float32)),
            jnp.asarray(np.zeros((qp, tp), np.float32)), n_docs=nb, k=k)
    if out is not None:
        jax.block_until_ready(out)


def warm(session, slack: int) -> str:
    shapes = device_shapes(session.server, session.dep.warren, session.pool,
                           slack)
    compile_shapes(shapes, session.cell.config["server"]["k"])
    return f"{len(shapes)} device shapes"


check = judge.run


def context(session) -> dict:
    return {"pool": session.pool, "kernel": "bm25_topk",
            "corpus_df": corpus_mod.document_frequency(session.dep.corpus)}
