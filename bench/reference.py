"""The plain reference: exhaustive BM25 over the benchmark's own corpus.

It imports nothing of the program.  Documents are the benchmark's passage
versions (word-rank arrays); a document's length is its word count, and
every word is a term (the corpus spells each word as its own Porter stem).
BM25 is Robertson's, with the idf ``log(1 + (N - df + 0.5) / (df + 0.5))``
and ``k1``, ``b`` from the configuration.  Scores are float64.  ``bf16``
is the rounding the control (``bench/control.py``) applies.
"""

from __future__ import annotations

import itertools
from typing import Iterable, List, Sequence, Tuple

import numpy as np


class Postings:
    """Term → (document ids, term frequencies) over a fixed set of
    documents, in compressed-row form."""

    def __init__(self, docs: Sequence[np.ndarray]):
        lens = np.array([len(d) for d in docs], np.int64)
        doc_of = np.repeat(np.arange(len(docs), dtype=np.int64), lens)
        ranks = (np.concatenate(docs).astype(np.int64) if len(docs)
                 else np.zeros(0, np.int64))
        key = ranks * len(docs) + doc_of
        key, tf = np.unique(key, return_counts=True)
        terms, self.doc = np.divmod(key, len(docs)) if len(docs) else (key, key)
        self.tf = tf.astype(np.float64)
        self.n_terms = int(terms.max()) + 1 if len(terms) else 1
        self.start = np.searchsorted(terms, np.arange(self.n_terms + 1))
        self.dl = lens.astype(np.float64)
        self.n_docs = len(docs)

    def of(self, term: int) -> Tuple[np.ndarray, np.ndarray]:
        if term >= self.n_terms:
            return self.doc[:0], self.tf[:0]
        a, b = self.start[term], self.start[term + 1]
        return self.doc[a:b], self.tf[a:b]


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float64 to the nearest bfloat16 (ties to even), as float64."""
    f = x.astype(np.float32).view(np.uint32).astype(np.uint64)
    f = (f + 0x7FFF + ((f >> 16) & 1)) & 0xFFFF0000
    return f.astype(np.uint32).view(np.float32).astype(np.float64)


class BM25:
    """Exhaustive BM25 over the live documents of a :class:`Postings`."""

    def __init__(self, postings: Postings, k1: float, b: float):
        self.p = postings
        self.k1, self.b = k1, b

    def scores(self, terms: Iterable[int], live: np.ndarray, n_live: int,
               sum_dl: float) -> np.ndarray:
        """Dense scores of every document under the live mask ``live``
        (``n_live`` and ``sum_dl`` describe it); non-live documents get 0."""
        avgdl = sum_dl / n_live
        out = np.zeros(self.p.n_docs)
        for t in dict.fromkeys(terms):
            doc, tf = self.p.of(t)
            keep = live[doc]
            doc, tf = doc[keep], tf[keep]
            if not len(doc):
                continue
            idf = np.log(1.0 + (n_live - len(doc) + 0.5) / (len(doc) + 0.5))
            denom = tf + self.k1 * (1.0 - self.b + self.b * self.p.dl[doc]
                                    / avgdl)
            imp = idf * tf * (self.k1 + 1.0) / denom
            out[doc] += imp
        return out


def ranking(scores: np.ndarray, k: int) -> List[Tuple[int, float]]:
    """The top ``k`` documents by score (> 0), best first."""
    nz = np.flatnonzero(scores > 0)
    top = nz[np.argsort(-scores[nz], kind="stable")[:k]]
    return [(int(d), float(scores[d])) for d in top]


def compare(got: Sequence[Tuple[int, float]], scores: np.ndarray, k: int,
            rtol: float) -> Tuple[bool, float]:
    """``got``, a served top-k as (document, score) with documents named by
    the reference's ids (-1 for an address that names no document),
    against the reference's dense ``scores``.

    Returns (same, gap).  ``same``: every rank's score equals the
    reference's at that rank within ``rtol``, and every served document
    scores the same on the reference, so an id that differs from the
    reference's at some rank is a tie there.  ``gap`` is the widest
    relative difference between a served score and the reference's, at
    the same rank or for the same document (inf where the lengths or
    documents disagree)."""
    want = ranking(scores, k)
    if len(got) != len(want) or len({d for d, _ in got}) != len(got):
        return False, float("inf")
    gap = 0.0
    for (gd, gs), (_, ws) in zip(got, want):
        if gd < 0:
            return False, float("inf")
        hs = scores[gd]
        gap = max(gap, abs(gs - ws) / ws,
                  abs(gs - hs) / ws if hs > 0 else float("inf"))
    return gap <= rtol, gap


def first_match(got, bm: BM25, terms, live: np.ndarray, n_live: int,
                sum_dl: float, groups: Sequence[Sequence[Tuple[int, bool]]],
                k: int, rtol: float) -> Tuple[bool, float, int]:
    """``got`` against every state the read may have seen: from (``live``,
    ``n_live``, ``sum_dl``), each group's writes in flight, in the order
    the group published them, applied up to any prefix (``groups``: per
    group, (document, becomes live) pairs).  Returns (same, gap, states
    tried) of the state with the smallest gap; stops at a state that
    matches within ``rtol / 100``, the rounding of float32 sums, so the
    gap read is that of the state the read saw and not of a neighbour
    that happens to fall inside ``rtol``."""
    best, tried = (False, float("inf")), 0
    dl = bm.p.dl
    for cut in itertools.product(*(range(len(g) + 1) for g in groups)):
        lv = live
        n, s = n_live, sum_dl
        if any(cut):
            lv = live.copy()
            for g, c in zip(groups, cut):
                for d, on in g[:c]:
                    if lv[d] != on:
                        lv[d] = on
                        n += 1 if on else -1
                        s += dl[d] if on else -dl[d]
        tried += 1
        same, gap = compare(got, bm.scores(terms, lv, n, s), k, rtol)
        if gap < best[1]:
            best = (same, gap)
        if same and gap <= rtol / 100:
            return same, gap, tried
    return best[0], best[1], tried
