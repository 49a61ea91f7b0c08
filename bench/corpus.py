"""Seeded passage corpus and queries, shaped like MS MARCO passages.

Words follow a rank-frequency law of two regimes over an open rank space
(Ferrer i Cancho and Sole 2001): Zipf-Mandelbrot with exponent ``zipf_s``
up to rank ``zipf_core``, the core vocabulary, and exponent
``zipf_tail_s`` beyond it.  The tail makes the vocabulary grow with the
collection as Heaps' law says; the configuration fits it to MS MARCO's
own count of distinct terms.  Passage lengths are log-normal around 60
words.

Drawn ranks are renumbered densely in rank order, so word id 1 is the
most frequent word that occurs and ids run to the collection's vocabulary
size.  Word id ``i`` is spelled by :func:`word`: consonant-vowel syllables
and a final consonant that ends no Porter suffix, so every word is its
own Porter stem and no two words share one (``bench/tests`` checks this
against the program's stemmer).  The reference can then score words as
terms without stemming.

Everything here is numpy on the host; nothing touches the program.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

_ONSETS = "bdfghklmnprstvz"
_VOWELS = "aeiou"
# final letters that end no Porter suffix (s, e, d, g, y, l, ... all do)
_FINALS = "bfkpvxz"
_SYLLABLES = [c + v for c in _ONSETS for v in _VOWELS]
# a configuration's keys that fix its collection
CORPUS_KEYS = ("passages", "corpus_seed", "zipf_s", "zipf_q", "zipf_core",
               "zipf_tail_s", "len_median", "len_sigma", "len_min", "len_max")
_MAX_RANK = 1 << 50


def word(rank: int) -> str:
    """The word of id ``rank`` (1-based); a bijection onto words of one or
    more syllables and a final consonant."""
    n, final = divmod(rank - 1, len(_FINALS))
    base, length = len(_SYLLABLES), 1
    while n >= base ** length:
        n -= base ** length
        length += 1
    out = []
    for _ in range(length):
        n, d = divmod(n, base)
        out.append(_SYLLABLES[d])
    return "".join(reversed(out)) + _FINALS[final]


def zipf_cdf(n_ranks: int, s: float, q: float) -> np.ndarray:
    """Cumulative Zipf-Mandelbrot probabilities over ranks 1..n_ranks."""
    p = 1.0 / (np.arange(1, n_ranks + 1) + q) ** s
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def draw_ranks(rng: np.random.Generator, cdf: np.ndarray,
               size) -> np.ndarray:
    """Ranks (1-based) drawn by inverse CDF."""
    return np.searchsorted(cdf, rng.random(size), side="right") + 1


class WordLaw:
    """The two-regime rank-frequency law of a configuration.

    Weight ``(r + q) ** -s`` for ranks ``r <= core``; beyond, the weight
    continues with exponent ``tail_s`` from the same value at ``core``,
    taken as a density over ``[core + 1/2, inf)`` and rounded to ranks, so
    the rank space has no end."""

    def __init__(self, spec: dict):
        s, q = spec["zipf_s"], spec["zipf_q"]
        self.core, self.q, self.tail_s = spec["zipf_core"], q, \
            spec["zipf_tail_s"]
        w = (np.arange(1, self.core + 1) + q) ** -s
        lo = self.core + 0.5 + q
        tail = ((self.core + q) ** (self.tail_s - s) * lo ** (1 - self.tail_s)
                / (self.tail_s - 1))
        total = w.sum() + tail
        self.core_cdf = np.cumsum(w) / total
        self.p_core = self.core_cdf[-1]

    def draw(self, rng: np.random.Generator, size: int) -> np.ndarray:
        u = rng.random(size)
        out = np.searchsorted(self.core_cdf, u, side="right") + 1
        t = u >= self.p_core
        v = (u[t] - self.p_core) / (1.0 - self.p_core)
        x = ((self.core + 0.5 + self.q)
             * (1.0 - v) ** (-1.0 / (self.tail_s - 1)) - self.q)
        out = out.astype(np.int64)
        out[t] = np.clip(np.rint(x), self.core + 1, _MAX_RANK).astype(np.int64)
        return out


@dataclasses.dataclass
class Corpus:
    """Passages as word-id arrays; ``offsets[i]:offsets[i+1]`` is passage
    i."""
    ranks: np.ndarray       # int32, every token's word id
    offsets: np.ndarray     # int64, len n + 1

    @property
    def n(self) -> int:
        return len(self.offsets) - 1

    @property
    def vocabulary(self) -> int:
        return int(self.ranks.max()) if len(self.ranks) else 0

    def tokens(self, i: int) -> np.ndarray:
        return self.ranks[self.offsets[i]:self.offsets[i + 1]]

    def text(self, i: int) -> str:
        return " ".join(word(int(r)) for r in self.tokens(i))


def passage_lengths(rng: np.random.Generator, n: int, spec: dict
                    ) -> np.ndarray:
    lens = np.rint(rng.lognormal(np.log(spec["len_median"]),
                                 spec["len_sigma"], n))
    return np.clip(lens, spec["len_min"], spec["len_max"]).astype(np.int64)


def make_corpus(spec: dict) -> Corpus:
    """The configuration's collection, from its own ``corpus_seed``."""
    rng = np.random.default_rng(spec["corpus_seed"])
    lens = passage_lengths(rng, spec["passages"], spec)
    offsets = np.concatenate([[0], np.cumsum(lens)])
    raw = WordLaw(spec).draw(rng, int(offsets[-1]))
    _, dense = np.unique(raw, return_inverse=True)
    return Corpus((dense.reshape(-1) + 1).astype(np.int32), offsets)


def known_item_queries(c: Corpus, q: dict, rng: np.random.Generator,
                       n: int) -> List[List[int]]:
    """``n`` queries, each a list of distinct word ids, made as simulated
    known-item queries (Azzopardi, de Rijke and Balog, SIGIR 2007): a
    target passage drawn uniformly, its term count ``min_terms +
    Binomial(max_terms - min_terms, 1/2)`` (2 to 10, mean 6: MS MARCO's
    query length), and terms drawn from the passage without replacement
    in proportion to their frequency there (their "popular" model).  As in
    MS MARCO, every query has a passage that answers it."""
    out = []
    targets = rng.integers(0, c.n, n)
    counts = q["min_terms"] + rng.binomial(q["max_terms"] - q["min_terms"],
                                           0.5, n)
    for p, k in zip(targets, counts):
        words, tf = np.unique(c.tokens(int(p)), return_counts=True)
        k = min(int(k), len(words))
        pick = rng.choice(len(words), k, replace=False, p=tf / tf.sum())
        out.append([int(w) for w in words[pick]])
    return out


def new_version(c: Corpus, spec: dict, rng: np.random.Generator
                ) -> np.ndarray:
    """An update's new text: a passage length drawn as the collection's,
    and tokens drawn from the collection's own token stream, so its words
    follow the collection's frequencies."""
    n = int(passage_lengths(rng, 1, spec)[0])
    return c.ranks[rng.integers(0, len(c.ranks), n)]


def query_text(terms: List[int]) -> str:
    return " ".join(word(r) for r in terms)


def document_frequency(c: Corpus) -> np.ndarray:
    """Passages containing each word id (index = id)."""
    doc = np.repeat(np.arange(c.n, dtype=np.int64), np.diff(c.offsets))
    key = np.unique(c.ranks.astype(np.int64) * c.n + doc)
    return np.bincount(key // c.n)
