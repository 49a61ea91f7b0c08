"""Dynamic index, Warren lifecycle, transactions, ACID, JSON store, ranking."""

import threading

import numpy as np
import pytest

from repro.core import (DynamicIndex, Warren, add_json, annotate_dates,
                        collection_stats, expand_query, index_document,
                        score_blockmax, score_bm25, score_wand, value_of,
                        build_block_impacts, porter_stem)
from repro.core.index import ERASE_FEATURE


def make_warren(log_path=None):
    return Warren(DynamicIndex(log_path=log_path))


def test_append_translate_roundtrip():
    w = make_warren()
    with w:
        w.transaction()
        lo, hi = w.append("To be or not to be, that is the question")
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        assert w.translate(lo, hi) == "To be or not to be, that is the question"
        assert w.translate(lo, lo + 5) == "To be or not to be"
        assert w.tokens(lo, lo + 1) == ["to", "be"]


def test_word_annotations_automatic():
    w = make_warren()
    with w:
        w.transaction()
        lo, hi = w.append("the cat sat on the mat")
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        cat = w.annotations("cat")
        assert list(cat) == [(lo + 1, lo + 1, 0.0)]
        the = w.annotations("the")
        assert [t[0] for t in the] == [lo, lo + 4]


def test_snapshot_isolation():
    w = make_warren()
    with w:
        w.transaction()
        w.append("first doc here")
        w.commit()
    reader = w.clone()
    reader.start()
    before = len(reader.annotations("doc"))
    writer = w.clone()
    with writer:
        writer.transaction()
        writer.append("second doc here")
        writer.commit()
    # reader still sees the old snapshot
    assert len(reader.annotations("doc")) == before == 1
    reader.end()
    reader.start()
    assert len(reader.annotations("doc")) == 2
    reader.end()


def test_abort_leaves_gap_and_no_annotations():
    w = make_warren()
    with w:
        w.transaction()
        w.append("visible words")
        w.commit()
    with w:
        w.transaction()
        lo, hi = w.append("phantom words")
        w.ready()
        w.abort()
    with w:
        assert len(w.annotations("phantom")) == 0
    # the aborted interval is a gap: next commit lands after it
    with w:
        w.transaction()
        lo2, _ = w.append("after gap")
        remap = w.commit()
    lo2 = remap(lo2)
    with w:
        assert lo2 > 1  # address space advanced past the gap
        assert w.translate(lo2, lo2) == "after"


def test_late_annotation_of_earlier_content():
    """The defining feature: annotate content appended by a previous txn."""
    w = make_warren()
    with w:
        w.transaction()
        lo, hi = w.append("some earlier content")
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        w.transaction()
        w.annotate("sentence:", lo, hi, 3.0)
        w.commit()
    with w:
        got = list(w.annotations("sentence:"))
        assert got == [(lo, hi, 3.0)]


def test_erase_hides_content_and_annotations():
    w = make_warren()
    with w:
        w.transaction()
        lo1, hi1 = w.append("doc one alpha")
        w.annotate(":", lo1, hi1)
        lo2, hi2 = w.append("doc two beta")
        w.annotate(":", lo2, hi2)
        remap = w.commit()
    lo1, hi1, lo2, hi2 = remap(lo1), remap(hi1), remap(lo2), remap(hi2)
    with w:
        w.transaction()
        w.erase(lo1, hi1)
        w.commit()
    with w:
        assert w.translate(lo1, hi1) is None
        assert len(w.annotations("alpha")) == 0
        assert len(w.annotations("beta")) == 1
        roots = w.annotations(":")
        assert list(roots) == [(lo2, hi2, 0.0)]


def test_nesting_conflict_keeps_innermost_and_seqnum_tiebreak():
    w = make_warren()
    with w:
        w.transaction()
        lo, hi = w.append("a b c d e f")
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        w.transaction()
        w.annotate("mark:", lo, hi, 1.0)       # outer
        w.commit()
    with w:
        w.transaction()
        w.annotate("mark:", lo + 1, lo + 2, 2.0)  # inner: wins
        w.annotate("same:", lo, lo + 1, 1.0)
        w.commit()
    with w:
        w.transaction()
        w.annotate("same:", lo, lo + 1, 9.0)   # same interval: larger seq wins
        w.commit()
    with w:
        assert list(w.annotations("mark:")) == [(lo + 1, lo + 2, 2.0)]
        assert list(w.annotations("same:")) == [(lo, lo + 1, 9.0)]


def test_durability_and_recovery(tmp_path):
    path = str(tmp_path / "txn.log")
    w = make_warren(path)
    with w:
        w.transaction()
        lo, hi = w.append("durable little document")
        w.annotate(":", lo, hi)
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        w.transaction()
        w.append("uncommitted stuff")
        w.ready()
        # crash before commit: simply drop the txn (no commit record)
    w.index._log.close()

    recovered = Warren(DynamicIndex.recover(path))
    with recovered:
        assert recovered.translate(lo, hi) == "durable little document"
        assert len(recovered.annotations("uncommitted")) == 0
        assert len(recovered.annotations("durable")) == 1
    # new writes allocate past the aborted interval
    with recovered:
        recovered.transaction()
        lo2, _ = recovered.append("post recovery")
        remap = recovered.commit()
    assert remap(lo2) >= hi + 1


def test_merge_segments_compacts(tmp_path):
    path = str(tmp_path / "txn.log")
    w = make_warren(path)
    for i in range(8):
        with w:
            w.transaction()
            lo, hi = w.append(f"document number {i} payload")
            w.annotate(":", lo, hi)
            w.commit()
    with w:
        w.transaction()
        docs = w.annotations(":")
        w.erase(int(docs.starts[0]), int(docs.ends[0]))
        w.commit()
    w.index.merge_segments()
    assert len(w.index._segments) == 1
    with w:
        assert len(w.annotations(":")) == 7
        assert len(w.annotations("number")) == 7
    # recovery from the compacted log
    w.index._log.close()
    rec = Warren(DynamicIndex.recover(path))
    with rec:
        assert len(rec.annotations(":")) == 7


def test_concurrent_readers_writers():
    """Many writers + readers; every snapshot internally consistent."""
    w = make_warren()
    stop = threading.Event()
    errors = []

    def writer(tid):
        wc = w.clone()
        for i in range(20):
            with wc:
                wc.transaction()
                index_document(wc, f"thread {tid} doc {i} words shared zebra")
                wc.commit()

    def reader():
        rc = w.clone()
        while not stop.is_set():
            with rc:
                docs = rc.annotations(":")
                dls = rc.annotations("dl:")
                # consistency: every committed doc has its dl: annotation
                if len(docs) != len(dls):
                    errors.append((len(docs), len(dls)))

    writers = [threading.Thread(target=writer, args=(t,)) for t in range(6)]
    readers = [threading.Thread(target=reader) for _ in range(4)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors, f"inconsistent snapshots: {errors[:5]}"
    with w:
        assert len(w.annotations(":")) == 120


# ------------------------------------------------------------------ #
# JSON store
# ------------------------------------------------------------------ #
SAMPLE = {
    "id": "0001", "type": "donut", "name": "Cake", "ppu": 0.55,
    "batters": {"batter": [{"id": "1001", "type": "Regular"},
                           {"id": "1002", "type": "Chocolate"}]},
    "topping": [{"id": "5001", "type": "None"},
                {"id": "5002", "type": "Glazed"}],
}


def test_json_store_paths_values_and_translate():
    w = make_warren()
    with w:
        w.transaction()
        lo, hi = add_json(w, SAMPLE, collection="Files/sample.json")
        remap = w.commit()
    lo, hi = remap(lo), remap(hi)
    with w:
        # root and collection features
        assert list(w.annotations(":"))[0][:2] == (lo, hi)
        assert list(w.annotations("Files/sample.json"))[0][:2] == (lo, hi)
        # nested path feature
        t = list(w.annotations(":batters:batter:[1]:type:"))
        assert len(t) == 1
        assert value_of(w, int(t[0][0]), int(t[0][1])) == "chocolate"
        # numeric value stored as annotation value
        ppu = list(w.annotations(":ppu:"))
        assert ppu[0][2] == pytest.approx(0.55)
        # array length as value
        arr = list(w.annotations(":batters:batter:"))
        assert arr[0][2] == 2.0
        # structural containment: type value inside element 1 extent
        el = list(w.annotations(":batters:batter:[1]:"))[0]
        assert el[0] <= t[0][0] and t[0][1] <= el[1]


def test_json_heterogeneous_dates():
    w = make_warren()
    objs = [
        {"name": "a", "created": "Feb 20 2015"},
        {"name": "b", "created_at": {"$date": 1180075887000}},  # 2007-05-25
        {"name": "c", "created": "2008-12-01T10:00:00"},
        {"name": "d"},
    ]
    with w:
        w.transaction()
        for o in objs:
            add_json(w, o, collection="Files/mixed.json")
        w.commit()
    with w:
        w.transaction()
        n = annotate_dates(w, [":created:", ":created_at:$date:"])
        w.commit()
    assert n == 3
    with w:
        y2008 = w.hopper("year=2008")
        roots = w.hopper(":")
        from repro.core.gcl import Containing
        got = Containing(roots, y2008).solutions()
        assert len(got) == 1


# ------------------------------------------------------------------ #
# ranking
# ------------------------------------------------------------------ #
DOCS = [
    "the quick brown fox jumps over the lazy dog",
    "a quick brown cat sleeps on the warm mat",
    "foxes and dogs are natural enemies said the fox",
    "the stock market rallied as tech shares jumped",
    "lazy afternoons with a good book and warm tea",
    "the fox hunted the quick rabbit through the brush",
]


def ranked_index():
    w = make_warren()
    with w:
        w.transaction()
        for i, d in enumerate(DOCS):
            index_document(w, d, docid=str(i))
        w.commit()
    return w


def test_bm25_sanity():
    w = ranked_index()
    with w:
        stats = collection_stats(w)
        assert stats.n_docs == len(DOCS)
        top = score_bm25(w, "quick fox", k=3, stats=stats)
        assert top, "no results"
        best = w.translate(top[0][0], int(stats.doc_ends[list(stats.doc_starts).index(top[0][0])]))
        assert "fox" in best



def test_bm25_ties_at_k_keep_lowest_addresses():
    w = make_warren()
    with w:
        w.transaction()
        for i in range(6):                      # six exact ties on "fox"
            index_document(w, f"fox filler{i} pad", docid=str(i))
        index_document(w, "fox fox pad", docid="best")
        w.commit()
    with w:
        stats = collection_stats(w)
        best = int(w.annotations("docid:best").starts[0])
        top = score_bm25(w, "fox", k=3, stats=stats)
        # the k boundary falls inside the tie: its lowest addresses stay
        assert [d for d, _ in top] == [best, int(stats.doc_starts[0]),
                                       int(stats.doc_starts[1])]
        assert top[1][1] == top[2][1] < top[0][1]
        full = score_bm25(w, "fox", k=7, stats=stats)
        for k in range(1, 8):                   # every k: a prefix
            assert score_bm25(w, "fox", k=k, stats=stats) == full[:k]

def test_wand_and_blockmax_match_exhaustive():
    w = ranked_index()
    with w:
        stats = collection_stats(w)
        def canon(res):
            return sorted(((d, round(s, 9)) for d, s in res),
                          key=lambda t: (-t[1], t[0]))

        for q in ["quick fox", "lazy dog warm", "stock market", "fox"]:
            exact = score_bm25(w, q, k=4, stats=stats)
            wand = score_wand(w, q, k=4, stats=stats)
            assert canon(wand) == canon(exact)
            bidx = build_block_impacts(w, list(dict.fromkeys(q.split())),
                                       block_size=2, stats=stats)
            bm = score_blockmax(bidx, k=4)
            assert canon(bm) == canon(exact)


def test_prf_expansion_adds_terms():
    w = ranked_index()
    with w:
        weights = expand_query(w, "fox", fb_docs=3, fb_terms=5)
        assert "fox" in weights
        assert len(weights) > 1
        top = score_bm25(w, "", k=3, weights=weights)
        assert top


def test_porter_examples():
    cases = {"caresses": "caress", "ponies": "poni", "relational": "relat",
             "conditional": "condit", "rational": "ration",
             "hopping": "hop", "falling": "fall", "happy": "happi",
             "electricity": "electr", "adjustable": "adjust"}
    for w, s in cases.items():
        assert porter_stem(w) == s, (w, porter_stem(w), s)
