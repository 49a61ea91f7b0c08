"""The plain reference against hand numbers and against the program's own
exhaustive scorer, and its lower-precision control."""

import math

import numpy as np
import pytest

from kinds.passages import corpus, reference


def _docs():
    return [np.array([1, 2, 2, 3]), np.array([2, 4]), np.array([1, 1, 5, 6, 7])]


def test_bm25_by_hand():
    post = reference.Postings(_docs())
    bm = reference.BM25(post, 0.9, 0.4)
    live = np.ones(3, bool)
    s = bm.scores([2], live, 3, float(post.dl.sum()))
    avgdl = 11 / 3
    idf = math.log(1 + (3 - 2 + 0.5) / (2 + 0.5))

    def imp(tf, dl):
        return idf * tf * 1.9 / (tf + 0.9 * (0.6 + 0.4 * dl / avgdl))
    assert s[0] == pytest.approx(imp(2, 4), rel=1e-12)
    assert s[1] == pytest.approx(imp(1, 2), rel=1e-12)
    assert s[2] == 0.0
    # a document that is not live neither scores nor counts in df, N, avgdl
    live[1] = False
    s = bm.scores([2], live, 2, 9.0)
    idf1 = math.log(1 + (2 - 1 + 0.5) / (1 + 0.5))
    assert s[1] == 0.0
    assert s[0] == pytest.approx(
        idf1 * 2 * 1.9 / (2 + 0.9 * (0.6 + 0.4 * 4 / 4.5)), rel=1e-12)


def test_compare_tie_rule_and_gap():
    scores = np.array([0.0, 3.0, 2.0, 2.0, 1.0])
    assert reference.compare([(1, 3.0), (3, 2.0)], scores, 2, 1e-5) == \
        (True, 0.0)
    same, gap = reference.compare([(1, 3.0), (4, 2.0)], scores, 2, 1e-5)
    assert not same and gap == pytest.approx(0.5)
    assert not reference.compare([(1, 3.0)], scores, 2, 1e-5)[0]
    assert not reference.compare([(1, 3.0), (1, 3.0)], scores, 2, 1e-5)[0]
    assert not reference.compare([(1, 3.0), (-1, 2.0)], scores, 2, 1e-5)[0]
    same, gap = reference.compare([(1, 3.0 * (1 + 2e-6)), (2, 2.0)], scores,
                                  2, 1e-5)
    assert same and gap == pytest.approx(2e-6)


def test_first_match_finds_the_state_a_read_saw():
    post = reference.Postings(_docs() + [np.array([2, 2, 2])])
    bm = reference.BM25(post, 0.9, 0.4)
    live = np.array([True, True, True, False])
    n, sdl = 3, float(post.dl[:3].sum())
    # the read saw document 3 appended and document 1 erased
    lv = np.array([True, False, True, True])
    seen = reference.ranking(bm.scores([2], lv, 3, float(post.dl[lv].sum())),
                             10)
    # the erase on one group, the append on another: 2 x 2 states
    same, gap, tried = reference.first_match(
        seen, bm, [2], live, n, sdl, [[(1, False)], [(3, True)]], 10, 1e-5)
    assert same and gap == 0.0 and tried == 4
    # both on one group, published erase first: a prefix, 3 states
    same, gap, tried = reference.first_match(
        seen, bm, [2], live, n, sdl, [[(1, False), (3, True)]], 10, 1e-5)
    assert same and tried == 3
    # published append first: the state with only the erase is no prefix
    only_erase = np.array([True, False, True, False])
    seen2 = reference.ranking(
        bm.scores([2], only_erase, 2, float(post.dl[only_erase].sum())), 10)
    same, _, _ = reference.first_match(
        seen2, bm, [2], live, n, sdl, [[(3, True), (1, False)]], 10, 1e-5)
    assert not same
    same, _, _ = reference.first_match(seen, bm, [2], live, n, sdl, [], 10,
                                       1e-5)
    assert not same


def test_bf16_rounds_to_8_bits_of_mantissa():
    x = np.array([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-9, 3.14159])
    got = reference.bf16(x)
    assert got[0] == 1.0 and got[1] == 1.0        # ties to even
    assert got[2] == 1.0 + 2**-7
    assert abs(got[3] - 3.14159) / 3.14159 < 2**-8


def test_reference_agrees_with_the_programs_exhaustive_scorer():
    """The same semantics as ``core.ranking.score_bm25`` over an index the
    program built from the same passages (the test may import the program;
    the reference does not)."""
    from repro.core import ranking
    from repro.core.index import DynamicIndex
    from repro.core.warren import Warren

    spec = {"passages": 200, "corpus_seed": 3, "zipf_s": 1.0, "zipf_q": 2.7,
            "zipf_core": 12000, "zipf_tail_s": 1.8, "len_median": 53,
            "len_sigma": 0.5, "len_min": 8, "len_max": 250}
    c = corpus.make_corpus(spec)
    w = Warren(DynamicIndex())
    starts = []
    with w:
        w.transaction()
        for i in range(c.n):
            starts.append(ranking.index_document(w, c.text(i))[0])
        remap = w.commit()
    starts = [remap(s) for s in starts]
    post = reference.Postings([c.tokens(i) for i in range(c.n)])
    bm = reference.BM25(post, 0.9, 0.4)
    live = np.ones(c.n, bool)
    rng = np.random.default_rng(0)
    with w:
        for _ in range(20):
            q = list(dict.fromkeys(int(r) for r in rng.integers(1, 200, 4)))
            text = corpus.query_text(q)
            want = ranking.score_bm25(w, text, k=c.n)
            got = bm.scores(q, live, c.n, float(post.dl.sum()))
            by_addr = {a: s for a, s in want}
            for i, a in enumerate(starts):
                assert got[i] == pytest.approx(by_addr.get(a, 0.0),
                                               rel=1e-9, abs=1e-12)


def test_scores_rounded_to_bf16_fail_the_comparison():
    """The control's rounding is far outside the tolerance."""
    c = corpus.make_corpus({"passages": 300, "corpus_seed": 5,
                            "zipf_s": 1.0, "zipf_q": 2.7, "zipf_core": 12000,
                            "zipf_tail_s": 1.8, "len_median": 53,
                            "len_sigma": 0.5, "len_min": 8, "len_max": 250})
    post = reference.Postings([c.tokens(i) for i in range(c.n)])
    bm = reference.BM25(post, 0.9, 0.4)
    live = np.ones(c.n, bool)
    sdl = float(post.dl.sum())
    for q in ([1, 7, 30], [2, 5], [11, 40, 90, 3]):
        scores = bm.scores(q, live, c.n, sdl)
        got = [(d, float(reference.bf16(np.array([s]))[0]))
               for d, s in reference.ranking(scores, 10)]
        same, gap = reference.compare(got, scores, 10, 1e-5)
        assert not same and gap > 1e-4
