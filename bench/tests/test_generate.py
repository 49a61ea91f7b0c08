import numpy as np
import pytest

import cells
import generate
from conftest import ROOT
from kinds import passages
from kinds.passages import corpus

BIG_SEED = 2**31 + 12345


def _config_and_mix(cell):
    c = cells.load(cell, ROOT)
    return c.config, c.mix


@pytest.fixture(scope="module")
def small():
    cfg, _ = _config_and_mix("passage.steady")
    cfg = dict(cfg, passages=2000)
    return cfg, passages.corpus_of(cfg)


def test_corpus_is_the_configurations_own(small):
    cfg, a = small
    b = corpus.make_corpus(cfg)
    assert np.array_equal(a.ranks, b.ranks)
    assert np.array_equal(a.offsets, b.offsets)
    # word ids are dense: every id up to the vocabulary occurs
    assert len(np.unique(a.ranks)) == a.vocabulary == a.ranks.max()
    lens = np.diff(a.offsets)
    assert lens.min() >= cfg["len_min"] and lens.max() <= cfg["len_max"]


def test_vocabulary_grows_as_heaps_law_says():
    """The open rank space keeps adding words: the vocabulary grows about
    as the square root of the tokens or faster, where a capped one
    would stop."""
    cfg, _ = _config_and_mix("passage.steady")
    v = [corpus.make_corpus(dict(cfg, passages=n)).vocabulary
         for n in (1000, 4000)]
    assert v[1] > 1.9 * v[0]
    assert 30000 < v[1] < 45000


def test_same_seed_same_schedule_other_seed_same_work_other_order(small):
    cfg, c = small
    _, mix = _config_and_mix("acid.ycsb-b")
    a = generate.open_schedule(mix, passages, cfg, BIG_SEED, 10.0)
    b = generate.open_schedule(mix, passages, cfg, BIG_SEED, 10.0)
    d = generate.open_schedule(mix, passages, cfg, BIG_SEED + 1, 10.0)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.query, b.query)
    assert [u.passage for u in a.updates if u] == \
        [u.passage for u in b.updates if u]
    assert not np.array_equal(a.due, d.due)
    assert not np.array_equal(a.query, d.query)
    # every seed: the same requests and the same gaps, in another order
    assert a.queries == d.queries
    assert sorted(u.passage for u in a.updates if u) == \
        sorted(u.passage for u in d.updates if u)
    ga = np.sort(np.diff(np.concatenate([[0.0], a.due, [10.0]])))
    gd = np.sort(np.diff(np.concatenate([[0.0], d.due, [10.0]])))
    assert np.allclose(ga, gd, atol=1e-9)
    n = round(mix["rate_per_s"] * 10.0)
    for s in (a, d):
        assert len(s.due) == n
        assert sum(u is not None for u in s.updates) == \
            round(mix["requests"]["update"] * n)
        assert np.all(np.diff(s.due) >= 0) and s.due.max() < 10.0
        assert np.all((s.query == -1) == np.array(
            [u is not None for u in s.updates]))
        assert sorted(s.query[s.query >= 0]) == list(range(len(s.queries)))


def test_warm_up_stream_has_queries_of_its_own(small):
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    a = generate.open_schedule(mix, passages, cfg, 7, 5.0, stream=0)
    b = generate.open_schedule(mix, passages, cfg, 7, 5.0, stream=1)
    assert a.queries != b.queries


def test_known_item_queries_follow_ms_marco_and_are_mostly_unique(small):
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    qs = passages.queries(mix, c, 0, 3000)
    lens = np.array([len(q) for q in qs])
    assert lens.min() >= 1 and lens.max() <= 10
    assert 5.3 < lens.mean() < 6.3
    assert all(len(set(q)) == len(q) for q in qs)
    # every query's words come from one passage
    sets = [set(map(int, c.tokens(i))) for i in range(c.n)]
    for q in qs[:50]:
        assert any(set(q) <= s for s in sets)
    assert len({tuple(sorted(q)) for q in qs}) > 0.95 * len(qs)


def test_df_ceiling_keeps_only_rare_words(small):
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    rare = dict(mix, queries=dict(mix["queries"], max_df_share=0.01))
    df = corpus.document_frequency(c)
    qs = passages.queries(rare, c, 0, 200, df)
    assert len(qs) == 200
    assert all(df[t] <= 0.01 * c.n for q in qs for t in q)
    assert all(len(q) >= mix["queries"]["min_terms"] for q in qs)


def test_an_arrival_pattern_shapes_the_rate(small):
    """On/off bursts, given as data: three times the rate for 1 s, a half
    for 4 s, repeated; the mean rate and the count stay the mix's."""
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    burst = dict(mix, rate_per_s=100, pattern=[[1.0, 3.0], [4.0, 0.5]])
    s = generate.open_schedule(burst, passages, cfg, BIG_SEED, 20.0)
    assert len(s.due) == 2000
    in_burst = (s.due % 5.0) < 1.0
    assert 0.5 < in_burst.mean() < 0.7           # 3 / (3 + 2) of requests


def test_closed_loop_plan_is_seeded_and_deals_every_query(small):
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    closed = dict(mix, loop="closed", clients=4, set_size=40)
    a = generate.closed_plan(closed, passages, cfg, BIG_SEED)
    b = generate.closed_plan(closed, passages, cfg, BIG_SEED)
    d = generate.closed_plan(closed, passages, cfg, BIG_SEED + 1)
    assert np.array_equal(a.order, b.order)
    assert not np.array_equal(a.order, d.order)
    assert sorted(np.concatenate([a.of(k) for k in range(4)])) == \
        list(range(40))


def test_unknown_request_kind_is_refused(small):
    cfg, c = small
    _, mix = _config_and_mix("passage.steady")
    with pytest.raises(ValueError, match=r"\['query', 'update'\]"):
        generate.open_schedule(dict(mix, requests={"scan": 1.0}), passages,
                               cfg, 1, 1.0)


def test_words_are_their_own_distinct_porter_stems():
    from repro.core.stemmer import porter_stem
    ids = list(range(1, 5000)) + list(range(130000, 135000))
    words = [corpus.word(r) for r in ids]
    assert len(set(words)) == len(words)
    assert all(porter_stem(w) == w for w in words)


def test_zipf_head_and_long_tail(small):
    _, c = small
    df = corpus.document_frequency(c)
    # the head word is in most passages, the tail in few
    assert df.max() > 0.5 * c.n
    assert (df[df > 0] < 0.01 * c.n).sum() > 0.5 * (df > 0).sum()
