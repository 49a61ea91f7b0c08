"""Serving loops: dynamic request batching + the two first-stage retrievers.

RetrievalServer serves ranked retrieval straight from an annotative index
(the paper's workload): queries are micro-batched, impacts are laid out in
the block-impact format, and scoring runs through either the exhaustive
device path or the Block-Max Pallas kernel.  Over a ``ShardedWarren`` it
serves *natively*: each micro-batch fans out once per shard group (on the
warren's scatter pool when async scatter is enabled), every group packs its
own compact block with GLOBAL collection statistics (every posting of the
batch in one flat ``[P]`` array, no padded rows; ``bm25_topk``'s compact
form), per-group device ``bm25_topk`` dispatches overlap the next group's
packing, and a global k-way merge yields exactly the single-index results.

Beside BM25 text, the same batcher takes ``core.aggregate``'s
``AggregateRequest``: exact filter-and-aggregate queries over a JSON
collection (TPC-H Q1 and Q6 are two).  A batch splits by request type; the
aggregate half fans out once per group, where each group's columns stay
resident on the device for as long as its snapshot key holds, makes one
device call per group for every request of the batch, and adds the
groups' partial sums exactly on the host.

LMServer wraps the transformer decode path with a KV cache and a simple
continuous-batching slot scheduler.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import aggregate, collection_stats, ranking, vectorized
from repro.core.aggregate import AggregateRequest
from repro.core.vectorized import bm25_topk
from repro.dist.parallel import ScatterTimings


# compact blocks: a group's posting count rounds up to one of these
# buckets, geometric steps of at most 1.5x from the floor, so a block is at
# least two thirds postings once it is past the floor
POSTINGS_FLOOR = 1024
COMPILE_THREADS = 8


def posting_buckets(limit: int) -> List[int]:
    """The compact block sizes, ascending, up to the first >= ``limit``:
    each 1.5x the last, rounded down to a multiple of 128."""
    out = [POSTINGS_FLOOR]
    while out[-1] < limit:
        out.append(out[-1] * 3 // 2 // 128 * 128)
    return out


@dataclasses.dataclass
class BatcherConfig:
    max_batch: int = 16
    max_wait_ms: float = 2.0


class _BatchFailure:
    """A handler exception, boxed so waiters can tell it from a result."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _Handle:
    """One request's completion slot; ``get`` re-raises handler failures."""

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue(maxsize=1)

    def _put(self, item) -> None:
        self._q.put(item)

    def get(self, block: bool = True, timeout: Optional[float] = None):
        res = self._q.get(block, timeout)
        if isinstance(res, _BatchFailure):
            raise res.exc
        return res


class MicroBatcher:
    """Dynamic batching: collect up to max_batch requests or max_wait_ms.

    A handler exception fails only the requests of that batch — it is
    boxed, delivered to each waiter's handle (re-raised from ``get``), and
    the batching loop keeps serving later requests.
    """

    def __init__(self, handler: Callable[[List[Any]], List[Any]],
                 cfg: BatcherConfig):
        self.handler = handler
        self.cfg = cfg
        self._q: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        # orders submit vs close-drain; contention-profiled
        # (lock_wait_ms{lock="microbatcher"})
        self._close_lock = obs.ProfiledLock("microbatcher")
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def submit(self, request) -> _Handle:
        done = _Handle()
        with self._close_lock:
            if self._stop.is_set():
                done._put(_BatchFailure(RuntimeError("MicroBatcher closed")))
                return done
            self._q.put((request, done, time.monotonic()))
        return done

    def _loop(self):
        while not self._stop.is_set():
            try:
                first = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            batch = [first]
            deadline = time.monotonic() + self.cfg.max_wait_ms / 1e3
            while len(batch) < self.cfg.max_batch:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    batch.append(self._q.get(timeout=remaining))
                except queue.Empty:
                    break
            reg = obs.registry()
            if reg.enabled:
                launch = time.monotonic()
                wait = reg.histogram(
                    "serve_queue_wait_ms",
                    "each request's wait from submit to its batch's launch")
                for _, _, t_submit in batch:
                    wait.observe(1e3 * (launch - t_submit))
                reg.gauge("serve_queue_depth",
                          "requests still queued when a batch launches"
                          ).set(self._q.qsize())
                reg.histogram("serve_batch_size",
                              "requests coalesced per micro-batch",
                              lo=0.5, hi=1e4, per_decade=40
                              ).observe(len(batch))
            try:
                with obs.span("serve.batch", size=len(batch)):
                    results = self.handler([r for r, _, _ in batch])
                if len(results) != len(batch):
                    raise RuntimeError(
                        f"handler returned {len(results)} results for a "
                        f"batch of {len(batch)}")
            except Exception as e:
                failure = _BatchFailure(e)
                for _, done, _ in batch:
                    done._put(failure)
                continue
            for (_, done, _), res in zip(batch, results):
                done._put(res)

    def close(self):
        """Stop the loop and promptly fail queued waiters — nobody blocks
        out their full timeout on a closed batcher."""
        with self._close_lock:    # no submit can slip in after the drain
            self._stop.set()
        self._thread.join(timeout=1.0)
        failure = _BatchFailure(RuntimeError("MicroBatcher closed"))
        while True:
            try:
                _, done, _ = self._q.get_nowait()
            except queue.Empty:
                break
            done._put(failure)


class RetrievalServer:
    """BM25 top-k over an annotative index with batched device scoring.

    Works over any object with the Warren read surface — a single
    ``Warren``, a ``ShardedWarren`` (with demoted cold groups), or a
    ``TieredWarren``, whose ``annotations`` already k-way merge the hot
    memtable with every on-disk static run, so scoring sees one logical
    hot+cold list per term.  After commits, tier freezes, or shard
    demotions change the collection, call :meth:`refresh_stats`.

    A ``ShardedWarren`` is served natively (scatter once per group, score
    per group, merge globally); ``timings`` holds the per-batch
    scatter/score/merge breakdown.
    """

    def __init__(self, warren, k: int = 10, batcher: BatcherConfig = None,
                 max_terms: int = 8, max_postings: int = 4096,
                 sharded_native: bool = True):
        self.warren = warren
        self.k = k
        self.max_terms = max_terms
        self.max_postings = max_postings
        self._sharded = sharded_native and hasattr(warren, "map_groups")
        self.timings = ScatterTimings()
        # device shapes already scored: a new shape tuple means the jitted
        # scorer compiles again — the counter Autopilot watches
        # to tell shape-bucket churn from steady-state serving
        self._seen_shapes: set = set()
        # compact blocks keep one row per batch slot, so their shapes vary
        # only with the posting bucket and the accumulator width
        batcher = batcher or BatcherConfig()
        self._query_slots = self._pad_sizes(batcher.max_batch, 1, 1)[0]
        self._warm_widths: set = set()
        # (group, collection) -> its resident columns; written only by the
        # group's own leg of a fan-out
        self._columns: Dict[Tuple[int, str], aggregate.ResidentColumns] = {}
        if self._sharded:
            self.stats = None    # the native path re-scatters per batch
        else:
            with warren:
                self.stats = collection_stats(warren)
        self.batcher = MicroBatcher(self._handle, batcher)

    def refresh_stats(self) -> None:
        """Re-derive collection statistics from a fresh snapshot; queries
        already in flight finish against the stats they started with.
        Reads through a clone so it never collides with the batcher
        thread's start()/end() bracket on the serving warren.  The native
        sharded path scatters fresh stats every batch, so there is
        nothing to refresh."""
        if self._sharded:
            return
        w = self.warren.clone()
        with w:
            self.stats = collection_stats(w)

    def timing_summary(self) -> str:
        return self.timings.summary()

    def query(self, text: str, timeout: float = 10.0):
        return self.batcher.submit(text).get(timeout=timeout)

    def _handle(self, queries: List[Any]) -> List[list]:
        # coalesce duplicate requests: a batch serves each distinct request
        # once, every waiter gets (a copy of) the shared result row
        uniq = list(dict.fromkeys(queries))
        texts = [q for q in uniq if not isinstance(q, AggregateRequest)]
        aggs = [q for q in uniq if isinstance(q, AggregateRequest)]
        by_query = {}
        if texts:
            by_query.update(zip(texts, self._handle_sharded(texts)
                                if self._sharded
                                else self._handle_single(texts)))
        if aggs:
            by_query.update(zip(aggs, self._handle_aggregate(aggs)))
        if len(uniq) == len(queries):
            return [by_query[q] for q in queries]
        # timings count served requests, so per-query figures stay
        # comparable with wall-clock ms/query over the same stream
        self.timings.add(queries=len(queries) - len(uniq))
        return [list(by_query[q]) for q in queries]

    def _query_terms(self, queries: List[str]) -> List[List[str]]:
        return [list(dict.fromkeys(ranking.ranking_tokens(q)))[:self.max_terms]
                for q in queries]

    @staticmethod
    def _cap_by_impact(di: np.ndarray, imp: np.ndarray,
                       limit: int) -> Tuple[np.ndarray, np.ndarray]:
        """Keep the top-``limit`` postings by impact (stable, so equal
        impacts keep address order) — truncating by document order would
        silently drop high-impact documents past the cap."""
        if len(di) <= limit:
            return di, imp
        keep = np.argsort(-imp, kind="stable")[:limit]
        return di[keep], imp[keep]

    def _pad_sizes(self, qn: int, nterms: int,
                   longest: int) -> Tuple[int, int, int]:
        """Stable-ish device shapes: the batch and term dims bucket to
        powers of two and the postings dim to a multiple of 256, so the
        jitted ``bm25_topk`` compiles a bounded set of shapes instead of
        one per (batch size, term count, longest list) — and short queries
        don't pay for ``max_terms`` worth of padded scatter work."""
        qp = max(1 << max(qn - 1, 0).bit_length(), 1)
        tp = min(self.max_terms, max(1 << max(nterms - 1, 0).bit_length(), 1))
        l = max(256, -(-longest // 256) * 256)
        return qp, tp, min(self.max_postings, l)

    def _acc_pad(self, n_docs: int) -> int:
        """Accumulator-size bucket: a power of two ≥ max(n_docs, k), so a
        commit changing the live document count doesn't recompile the
        jitted scorer.  Padded slots never receive impacts, score 0, and
        are filtered by the ``s > 0`` result guard."""
        return 1 << max(max(n_docs, self.k) - 1, 0).bit_length()

    def _note_shapes(self, *shape: int) -> None:
        """Count first sightings of a device shape bucket — each one is a
        fresh XLA compile of the jitted scorer.  ``shape`` is (qp, tp, l,
        nb) for the postings form, (qp, p, nb) for the compact form."""
        key = (*shape, self.k)
        if key not in self._seen_shapes:
            self._seen_shapes.add(key)
            reg = obs.registry()
            if reg.enabled:
                reg.counter(
                    "serve_jit_recompile_total",
                    "distinct (batch, terms, postings, accumulator) device "
                    "shape buckets scored — each costs one XLA compile"
                ).inc()

    def _warm_compact(self, qp: int, nb: int) -> None:
        """Compile the compact scorer for every posting bucket a batch of
        ``qp`` query slots can fill at accumulator width ``nb``, the first
        time a batch needs that width: the batches after it, whatever
        their postings, then compile nothing."""
        if (qp, nb) in self._warm_widths:
            return
        self._warm_widths.add((qp, nb))
        sizes = posting_buckets(qp * self.max_terms
                                * min(self.max_postings, nb))

        def one(p: int) -> None:
            vectorized.bm25_topk.lower(
                jax.ShapeDtypeStruct((p,), jnp.int32),
                jax.ShapeDtypeStruct((p,), jnp.float32),
                jax.ShapeDtypeStruct((qp, 1), jnp.float32),
                n_docs=nb, k=self.k).compile()

        with ThreadPoolExecutor(COMPILE_THREADS) as ex:
            list(ex.map(one, sizes))
        for p in sizes:
            self._note_shapes(qp, p, nb)

    # -- single-index path ------------------------------------------------- #
    def _handle_single(self, queries: List[str]
                       ) -> List[List[Tuple[int, float]]]:
        stats = self.stats      # one coherent stats version per batch
        qn, l_cap = len(queries), self.max_postings
        if stats.n_docs == 0:
            return [[] for _ in queries]
        t0 = time.perf_counter()
        entries: List[Tuple[int, int, np.ndarray, np.ndarray]] = []
        with self.warren:
            for qi, terms in enumerate(self._query_terms(queries)):
                for ti, term in enumerate(terms):
                    lst = self.warren.annotations(
                        ranking.TF_PREFIX + ranking.porter_stem(term))
                    if not len(lst):
                        continue
                    idf = ranking._bm25_idf(stats.n_docs, len(lst))
                    di, imp = ranking._impacts(lst, stats, idf,
                                               k1=0.9, b=0.4)
                    di, imp = self._cap_by_impact(di, imp, l_cap)
                    entries.append((qi, ti, di, imp))
        t_scatter = time.perf_counter() - t0
        t0 = time.perf_counter()
        qp, tp, l = self._pad_sizes(
            qn, max((e[1] + 1 for e in entries), default=1),
            max((len(e[2]) for e in entries), default=1))
        nb = self._acc_pad(stats.n_docs)
        self._note_shapes(qp, tp, l, nb)
        with obs.span("device_score"):
            with obs.phase_timer("bm25_topk", "gather"):
                doc_idx = np.full((qp, tp, l), nb, np.int32)
                impacts = np.zeros((qp, tp, l), np.float32)
                qmask = np.zeros((qp, tp), np.float32)
                for qi, ti, di, imp in entries:
                    doc_idx[qi, ti, :len(di)] = di
                    impacts[qi, ti, :len(di)] = imp
                    qmask[qi, ti] = 1.0
            with obs.phase_timer("bm25_topk", "compute"):
                scores, ids = bm25_topk(jnp.asarray(doc_idx),
                                        jnp.asarray(impacts),
                                        jnp.asarray(qmask),
                                        n_docs=nb, k=self.k)
                scores, ids = np.asarray(scores), np.asarray(ids)
        t_score = time.perf_counter() - t0
        t0 = time.perf_counter()
        with obs.span("merge"):
            out = []
            for qi in range(qn):
                res = [(int(stats.doc_starts[d]), float(s))
                       for d, s in zip(ids[qi], scores[qi]) if s > 0]
                out.append(res)
        t_merge = time.perf_counter() - t0
        self.timings.add(scatter=t_scatter, score=t_score, merge=t_merge,
                         queries=qn)
        return out

    # -- native ShardedWarren path ----------------------------------------- #
    def _global_impacts(self, stems: List[str], per: list, lists: list,
                        n_docs: int) -> dict:
        """Per stem, every group's (doc_idx, impact) arrays under GLOBAL
        df/idf and avgdl, or None when no group holds the stem."""
        l, n_groups = self.max_postings, len(per)
        # global stats, computed exactly as collection_stats would over the
        # merged surface (avgdl is order-free; ties merge by address below)
        avgdl = float(np.concatenate([s.doc_lens for s in per]).mean())
        # per stem: per-group (doc_idx, impact) with GLOBAL df/avgdl, then
        # the posting cap applied to the *global* list so the kept postings
        # are exactly the single-index path's
        term_group: Dict[str, Optional[List[Tuple[np.ndarray, np.ndarray]]]] \
            = {}
        empty = (np.zeros(0, np.int64), np.zeros(0))
        for si, f in enumerate(stems):
            df = sum(len(lists[g][si]) for g in range(n_groups))
            if df == 0:
                term_group[f] = None
                continue
            idf = ranking._bm25_idf(n_docs, df)
            per_g = []
            for g in range(n_groups):
                lst, stats = lists[g][si], per[g]
                if len(lst) == 0 or stats.n_docs == 0:
                    per_g.append(empty)
                    continue
                per_g.append(ranking._impacts_with_avgdl(lst, stats, idf,
                                                         avgdl))
            total = sum(len(di) for di, _ in per_g)
            if total > l:
                cat = np.concatenate([imp for _, imp in per_g])
                keep = np.zeros(total, bool)
                keep[np.argsort(-cat, kind="stable")[:l]] = True
                capped, off = [], 0
                for di, imp in per_g:
                    m = keep[off:off + len(di)]
                    off += len(di)
                    capped.append((di[m], imp[m]))
                per_g = capped
            term_group[f] = per_g
        return term_group

    def _handle_sharded(self, queries: List[str]
                        ) -> List[List[Tuple[int, float]]]:
        qn, k = len(queries), self.k
        qterms = self._query_terms(queries)
        # stem every query term once; pack_group indexes these features
        qfeatures = [[ranking.TF_PREFIX + ranking.porter_stem(term)
                      for term in terms] for terms in qterms]
        stems = list(dict.fromkeys(f for row in qfeatures for f in row))
        # scatter: ONE fan-out per group for the whole micro-batch — every
        # group returns its stats and its slice of every term list (the
        # fan-out follows the session's pinned routing table, so the group
        # count comes from the gather, not from the live warren)
        def read_group(w):
            with obs.span("scatter.stats"):
                stats = ranking.collection_stats(w)
            return stats, [w.annotations(f) for f in stems]

        t0 = time.perf_counter()
        with self.warren:
            gathered = self.warren.map_groups(read_group)
        t_scatter = time.perf_counter() - t0
        t0 = time.perf_counter()
        n_groups = len(gathered)
        per = [s for s, _ in gathered]
        lists = [lst for _, lst in gathered]
        n_docs = sum(s.n_docs for s in per)
        if n_docs == 0:
            self.timings.add(scatter=t_scatter, queries=qn)
            return [[] for _ in queries]
        with obs.phase_timer("bm25_topk", "impacts"):
            term_group = self._global_impacts(stems, per, lists, n_docs)

        qp = max(self._query_slots, self._pad_sizes(qn, 1, 1)[0])

        def pack_group(g: int):
            """This group's compact (doc_idx, impacts, qmask) block, its
            accumulator width and its posting count, or None when the group
            has no documents or no postings for the batch.  Every (query,
            term) slot's postings go in, a stem twice in one query twice."""
            ng = per[g].n_docs
            if ng == 0:
                return None
            parts, n = [], 0
            for qi, row in enumerate(qfeatures):
                for f in row:
                    per_g = term_group[f]
                    if per_g is not None and len(per_g[g][0]):
                        parts.append((qi, *per_g[g]))
                        n += len(per_g[g][0])
            if n == 0:          # nothing scored here: all-zero rows anyway
                return None
            nb = self._acc_pad(ng)
            self._warm_compact(qp, nb)
            p = posting_buckets(n)[-1]
            doc_idx = np.empty(p, np.int32)
            impacts = np.empty(p, np.float32)
            pos = 0
            for qi, di, imp in parts:
                end = pos + len(di)
                np.add(di, qi * nb, out=doc_idx[pos:end], casting="unsafe")
                impacts[pos:end] = imp
                pos = end
            doc_idx[n:] = qp * nb
            impacts[n:] = 0.0
            qmask = np.zeros((qp, 1), np.float32)
            qmask[:qn] = 1.0
            return doc_idx, impacts, qmask, nb, n

        # pipelined scoring: jax dispatch is asynchronous, so group g's
        # device top-k computes while group g+1's block is being packed;
        # the np.asarray collection below blocks on all of them at once
        with obs.span("device_score"):
            pending, h2d_bytes, postings, slots = [], 0, 0, 0
            for g in range(n_groups):
                with obs.phase_timer("bm25_topk", "gather"):
                    blk = pack_group(g)
                if blk is None:
                    pending.append(None)
                    continue
                doc_idx, impacts, qmask, nb, n = blk
                h2d_bytes += doc_idx.nbytes + impacts.nbytes + qmask.nbytes
                postings += n
                slots += len(doc_idx)
                # host arrays go to the call itself, which copies all
                # three at once: a jnp.asarray each waits out one copy's
                # latency after another
                with obs.phase_timer("bm25_topk", "dispatch"):
                    pending.append(bm25_topk(doc_idx, impacts, qmask,
                                             n_docs=nb, k=k))
            reg = obs.registry()
            if reg.enabled:
                reg.histogram("serve_h2d_bytes",
                              "bytes copied to the device per micro-batch "
                              "(every group's packed blocks)",
                              lo=1.0, hi=1e10).observe(h2d_bytes)
                reg.histogram("serve_scored_postings",
                              "postings sent to the device per micro-batch "
                              "(every group's compact blocks, no padding)",
                              lo=1.0, hi=1e10).observe(postings)
                reg.histogram("serve_scatter_slots",
                              "slots the device scatter-adds per micro-batch, "
                              "padding included",
                              lo=1.0, hi=1e10).observe(slots)
            with obs.phase_timer("bm25_topk", "compute"):
                group_res = [None if p is None
                             else (np.asarray(p[0]), np.asarray(p[1]))
                             for p in pending]
        t_score = time.perf_counter() - t0
        # gather: global k-way merge; per-group lists come out of top_k
        # sorted by (-score, doc index) = (-score, address) within a group,
        # and the composite key merges on the document's ADDRESS, which is
        # the single-index tie order no matter how rebalancing has
        # interleaved group address ranges
        t0 = time.perf_counter()
        with obs.span("merge"):
            out = []
            for qi in range(qn):
                runs = []
                for g, res in enumerate(group_res):
                    if res is None:
                        continue
                    sc, ids = res
                    runs.append([(-float(s), int(per[g].doc_starts[int(d)]))
                                 for s, d in zip(sc[qi], ids[qi]) if s > 0])
                merged = heapq.merge(*runs)   # key: (-score, address)
                row = [(addr, -neg_s)
                       for neg_s, addr in itertools.islice(merged, k)]
                out.append(row)
        t_merge = time.perf_counter() - t0
        self.timings.add(scatter=t_scatter, score=t_score, merge=t_merge,
                         queries=qn)
        return out

    # -- aggregate requests ------------------------------------------------ #
    def _resident(self, w, key, collection: str,
                  needed: set) -> aggregate.ResidentColumns:
        """The group's resident columns of ``collection`` for snapshot
        ``key``, holding at least ``needed``: rebuilt from the pinned
        snapshot ``w`` and uploaded when the key moved or a column is new
        (a miss), else the cached ones."""
        group = key[0]
        with obs.span("scatter.columns", group=group):
            got = self._columns.get((group, collection))
            if got is not None and got.key == key and needed <= set(got.specs):
                return got
            specs = sorted(needed | (set(got.specs) if got is not None
                                     else set()),
                           key=lambda c: (c.path, c.scale is None,
                                          c.scale or 0))
            got = aggregate.ResidentColumns(key, w, collection, specs)
            self._columns[(group, collection)] = got
            reg = obs.registry()
            if reg.enabled:
                reg.counter("serve_column_builds_total",
                            "resident column tables built (a snapshot key "
                            "moved or a column was new)", group=group).inc()
            return got

    def _handle_aggregate(self, requests: List[AggregateRequest]
                          ) -> List[List[tuple]]:
        by_coll: Dict[str, List[AggregateRequest]] = {}
        for r in requests:
            by_coll.setdefault(r.collection, []).append(r)
        needed = {c: {col for r in rs for col in r.columns}
                  for c, rs in by_coll.items()}

        def read_group(w, key):
            return {c: self._resident(w, key, c, needed[c]) for c in by_coll}

        t0 = time.perf_counter()
        with self.warren:
            gathered = self.warren.map_groups(read_group, keyed=True)
        t_scatter = time.perf_counter() - t0
        t0 = time.perf_counter()
        reg = obs.registry()
        if reg.enabled:
            reg.gauge("serve_column_bytes",
                      "bytes of every resident column table on the device"
                      ).set(sum(t.nbytes for t in self._columns.values()))
        qp = self._query_slots
        launched, products = [], {}
        with obs.phase_timer("agg_scan", "dispatch"):
            for coll, rs in by_coll.items():
                tables = [g[coll] for g in gathered]
                n_keys = aggregate.key_slots(tables, rs)
                products[coll] = aggregate.batch_products(rs)
                for t in tables:
                    params, factors = aggregate.encode(rs, t, qp,
                                                       products[coll])
                    self._note_shapes("agg_scan", qp, *t.shape, n_keys,
                                      len(factors))
                    launched.append(aggregate.scan(t, params, factors,
                                                   n_keys))
        with obs.phase_timer("agg_scan", "compute"):
            results = aggregate.fetch(launched)
        t_score = time.perf_counter() - t0
        if reg.enabled:
            reg.histogram("serve_agg_rows",
                          "rows the device scans per micro-batch (every "
                          "group's resident rows, once per collection)",
                          lo=1.0, hi=1e10).observe(
                sum(g[c].n_rows for g in gathered for c in by_coll))
            reg.histogram("serve_agg_products",
                          "distinct products the device scan forms per row "
                          "per micro-batch (every collection's)",
                          lo=1.0, hi=1e4).observe(
                sum(len(p) for p in products.values()))
        t0 = time.perf_counter()
        with obs.span("merge"):
            out, at = {}, 0
            for coll, rs in by_coll.items():
                partials = [(g[coll], results[at + i])
                            for i, g in enumerate(gathered)]
                at += len(gathered)
                for slot, r in enumerate(rs):
                    out[r] = aggregate.finalize(r, partials, slot,
                                                products[coll])
        t_merge = time.perf_counter() - t0
        self.timings.add(scatter=t_scatter, score=t_score, merge=t_merge,
                         queries=len(requests))
        return [out[r] for r in requests]

    def close(self):
        self.batcher.close()


class LMServer:
    """Continuous-batching decode server over the transformer decode path."""

    def __init__(self, params, cfg, max_slots: int = 8, max_len: int = 128):
        from repro.models import transformer as T
        self.T = T
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        self.cache = T.init_cache(cfg, max_slots, max_len)
        self.step_fn = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))
        self.slot_free = [True] * max_slots
        self.slot_out: List[List[int]] = [[] for _ in range(max_slots)]

    def generate(self, prompts: List[List[int]], max_new: int = 16
                 ) -> List[List[int]]:
        """Greedy-decode a batch of prompts (token-id lists)."""
        assert len(prompts) <= self.max_slots
        # a fresh KV cache per call: decoding against a previous call's
        # cache would attend to its keys/values and resume at its length
        self.cache = self.T.init_cache(self.cfg, self.max_slots, self.max_len)
        outs = [[] for _ in prompts]
        # prefill by stepping prompts token by token (cache fills)
        tokens = np.zeros((self.max_slots,), np.int32)
        max_prompt = max(len(p) for p in prompts)
        for i in range(max_prompt + max_new):
            for s, p in enumerate(prompts):
                if i < len(p):
                    tokens[s] = p[i]
            logits, self.cache = self.step_fn(self.params, self.cache,
                                              jnp.asarray(tokens))
            nxt = np.asarray(jnp.argmax(logits, -1))
            for s, p in enumerate(prompts):
                if i >= len(p) - 1:       # past the prompt: greedy decode
                    outs[s].append(int(nxt[s]))
                    tokens[s] = int(nxt[s])
        return [o[:max_new] for o in outs]
