"""vByte codec, static index, graph store."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (DynamicIndex, GraphStore, StaticIndex, Warren,
                        add_json, index_document, score_bm25, write_static)
from repro.core import vbyte


@settings(max_examples=80, deadline=None)
@given(st.lists(st.integers(0, 2**48), max_size=200))
def test_vbyte_roundtrip(values):
    arr = np.array(values, dtype=np.int64)
    enc = vbyte.encode(arr)
    dec = vbyte.decode(enc, len(arr))
    assert np.array_equal(dec, arr)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(-2**40, 2**40), max_size=100))
def test_zigzag_roundtrip(values):
    arr = np.array(values, dtype=np.int64)
    assert np.array_equal(vbyte.unzigzag(vbyte.zigzag(arr)), arr)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 2**32), min_size=1, max_size=100, unique=True))
def test_gap_roundtrip(values):
    arr = np.sort(np.array(values, dtype=np.int64))
    enc = vbyte.encode_gaps(arr)
    assert np.array_equal(vbyte.decode_gaps(enc, len(arr)), arr)


def test_static_index_roundtrip(tmp_path):
    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        for i in range(10):
            index_document(w, f"static document {i} with shared words fox")
        w.commit()
    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    assert len(si.annotations(":")) == 10
    assert len(si.annotations("fox")) == 10
    # ranking works against the static index too (same read surface)
    top = score_bm25(si, "fox shared", k=3)
    assert len(top) == 3
    # translate round trip
    doc0 = si.annotations(":")
    t = si.translate(int(doc0.starts[0]), int(doc0.ends[0]))
    assert t.startswith("static document 0")
    si.close()


def test_static_roundtrip_forced_zlib_fallback(tmp_path, monkeypatch):
    """write_static of a committed snapshot, re-read with the zlib codec
    path forced (as if zstandard were not installed): every blob must be
    self-describing and the erased state must survive the round trip."""
    from repro.core import codec

    monkeypatch.setattr(codec, "_zstd", None)

    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        for i in range(8):
            index_document(w, f"fallback document {i} shared fox",
                           docid=f"d{i}")
        w.commit()
    with w:
        lst = w.annotations("docid:d3")
        victim = (int(lst.starts[0]), int(lst.ends[0]))
    with w:
        w.transaction()
        w.erase(*victim)
        w.commit()

    d = str(tmp_path / "static")
    write_static(idx, d)
    si0 = StaticIndex(d)
    from repro.core.codec import ZLIB
    # the fallback really engaged: v2 content payloads are codec-tagged
    assert si0.content.raw_payload(0)[0] == ZLIB
    si0.close()

    si = StaticIndex(d)
    assert len(si.annotations(":")) == 7      # erased doc is gone
    assert len(si.annotations("docid:d3")) == 0
    # regression: erased CONTENT must not leak back through the static
    # layout — dynamic and static agree that the span is unreadable
    with w:
        assert w.translate(*victim) is None
    assert si.translate(*victim) is None
    assert si.tokens(*victim) is None
    # a partial overlap with the erased interval is unreadable too
    assert si.translate(victim[0] + 1, victim[1] + 1) is None
    surviving = si.annotations("docid:d0")
    t = si.translate(int(surviving.starts[0]), int(surviving.ends[0]))
    assert t == "fallback document 0 shared fox"
    top = score_bm25(si, "fox shared", k=3)
    assert len(top) == 3
    si.close()


def test_static_snapshot_parity_hopper_phrase_over_erased(tmp_path):
    """StaticIndex and Snapshot must agree on hopper access methods and
    phrase solutions when erased intervals cut through the collection:
    full-document erases, a partial mid-document erase, and probes that
    straddle an erased boundary."""
    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        for i in range(12):
            index_document(w, f"quick brown fox number {i} jumps high",
                           docid=f"d{i}")
        w.commit()
    spans = {}
    with w:
        for i in range(12):
            lst = w.annotations(f"docid:d{i}")
            spans[i] = (int(lst.starts[0]), int(lst.ends[0]))
    with w:
        w.transaction()
        w.erase(*spans[4])                       # full doc
        w.erase(spans[7][0] + 1, spans[7][0] + 3)  # partial, mid-doc
        w.commit()

    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    snap = idx.snapshot()

    for feature in ("quick", "brown", "fox", "jumps", ":", "dl:",
                    "docid:d4", "docid:d7"):
        fval = idx.featurizer.featurize(feature)
        assert si.annotations(feature) == snap.annotations(fval), feature

    # hopper access methods probed across the erased boundaries
    fval = idx.featurizer.featurize("fox")
    h_static, h_dyn = si.hopper("fox"), snap.hopper(fval)
    probes = [spans[4][0] - 1, spans[4][0], spans[4][1],
              spans[4][1] + 1, spans[7][0], spans[7][0] + 2, spans[7][1]]
    for k in probes:
        assert h_static.tau(k) == h_dyn.tau(k), k
        assert h_static.rho(k) == h_dyn.rho(k), k

    # phrase solutions: erased docs drop out identically on both sides
    w_static, w_dyn = si.phrase("quick brown fox"), None
    with w:
        w_dyn = w.phrase("quick brown fox")
        assert w_static.solutions() == w_dyn.solutions()
        assert len(w_static.solutions()) == 10   # d4 gone; d7 phrase cut
    # translate/tokens straddling the erased boundary: None on both sides
    for p, q in [(spans[4][0] - 1, spans[4][0]), (spans[7][0], spans[7][1]),
                 (spans[7][0] + 3, spans[7][0] + 4)]:
        with w:
            assert si.translate(p, q) is None
            assert si.translate(p, q) == w.translate(p, q)
            assert si.tokens(p, q) == w.tokens(p, q)
    si.close()


def test_static_legacy_meta_without_erased_fields(tmp_path):
    """Directories written before the erased list existed (no er_* keys in
    meta.msgpack) must load with nothing hidden."""
    import msgpack

    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        index_document(w, "legacy layout doc", docid="d0")
        w.commit()
    from repro.core.static import _write_static_v1

    d = str(tmp_path / "static")
    _write_static_v1(idx, d)
    with open(d + "/meta.msgpack", "rb") as fh:
        meta = msgpack.unpackb(fh.read(), raw=False)
    for k in ("er_n", "er_s", "er_e"):
        meta.pop(k)
    with open(d + "/meta.msgpack", "wb") as fh:
        fh.write(msgpack.packb(meta))
    si = StaticIndex(d)
    docs = si.annotations(":")
    assert len(docs) == 1
    assert si.translate(int(docs.starts[0]),
                        int(docs.ends[0])) == "legacy layout doc"
    si.close()


def test_codec_legacy_raw_zstd_frame_without_zstd(monkeypatch):
    """A pre-codec-byte blob (raw zstd frame) read in a zlib-only
    environment must fail loudly naming the missing codec — never be
    misparsed as an unknown codec byte."""
    from repro.core import codec

    monkeypatch.setattr(codec, "_zstd", None)
    legacy = b"\x28\xb5\x2f\xfd" + b"\x00" * 16   # zstd magic + frame bytes
    with np.testing.assert_raises(RuntimeError):
        codec.decompress(legacy)
    try:
        codec.decompress(legacy)
    except RuntimeError as e:
        assert "zstandard" in str(e)
    # zlib-tagged blobs always decode, zstd or not
    blob = codec.compress(b"fallback payload" * 10)
    assert blob[0] == codec.ZLIB
    assert codec.decompress(blob) == b"fallback payload" * 10


def test_codec_roundtrips_from_many_threads():
    """Log writers and readers compress and decompress at once: every
    thread's blobs must round-trip (one shared zstd context would not)."""
    import threading

    from repro.core import codec

    errors = []

    def worker(tid):
        rng = np.random.default_rng(tid)
        try:
            for i in range(200):
                data = rng.integers(0, 16, size=2000 + i,
                                    dtype=np.uint8).tobytes()
                if codec.decompress(codec.compress(data)) != data:
                    errors.append((tid, i))
        except Exception as e:          # noqa: BLE001 - reported below
            errors.append((tid, repr(e)))

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []


def test_graph_store_friends():
    w = Warren(DynamicIndex())
    g = GraphStore(w)
    with w:
        w.transaction()
        people = {}
        for name in ["Alice", "Bob", "Carol", "Dave"]:
            people[name] = g.add_node({"name": name})
        edges = {"Alice": ["Bob", "Carol", "Dave"], "Bob": ["Alice", "Dave"],
                 "Carol": ["Alice"], "Dave": ["Bob", "Alice"]}
        for src, dsts in edges.items():
            for dst in dsts:
                g.add_edge("@friend", people[src][0], people[dst][0])
        remap = w.commit()
    people = {k: (remap(lo), remap(hi)) for k, (lo, hi) in people.items()}
    with w:
        nbrs = g.neighbors("@friend", *people["Alice"])
        assert sorted(nbrs) == sorted([people[n][0] for n in ["Bob", "Carol", "Dave"]])
        # resolve a target address back to its containing object
        obj = g.containing_object(nbrs[0])
        assert obj in people.values()
        # BFS reaches everyone from Carol
        reached = list(g.bfs("@friend", people["Carol"]))
        assert len(reached) == 4


def test_graph_store_triples():
    w = Warren(DynamicIndex())
    g = GraphStore(w)
    with w:
        w.transaction()
        streep = g.add_node({"name": "Meryl Streep"})
        oscar = g.add_node({"name": "Best Actress"})
        g.add_triple(streep[0], "won_award", oscar[0])
        remap = w.commit()
    streep = (remap(streep[0]), remap(streep[1]))
    oscar = (remap(oscar[0]), remap(oscar[1]))
    with w:
        objs = g.objects_of(streep, "won_award")
        assert objs == [oscar[0]]


# ------------------------------------------------------------------ #
# v2 lazy decode: mmap blocks, erased unions, promotion parity
# ------------------------------------------------------------------ #
def test_lazy_content_multi_block_record_roundtrip(tmp_path):
    """A record bigger than several 4 KiB blocks reassembles exactly
    through the block reader (extent pinning across block boundaries)."""
    from repro.core.static import LazyContentStore

    idx = DynamicIndex()
    w = Warren(idx)
    long_text = " ".join(f"tok{i}" for i in range(4000))     # ~30 KiB
    with w:
        w.transaction()
        index_document(w, "tiny doc before", docid="small0")
        index_document(w, long_text, docid="big")
        index_document(w, "tiny doc after", docid="small1")
        w.commit()
    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    assert isinstance(si.content, LazyContentStore)
    lst = si.annotations("docid:big")
    p, q = int(lst.starts[0]), int(lst.ends[0])
    assert si.translate(p, q) == long_text
    assert si.tokens(p, q) == long_text.split()
    # and only the touched records were decoded (the corpus stays cold)
    assert len(si.content._lru) <= 2
    si.close()


def test_erased_union_through_mmap_blocks(tmp_path):
    """Tombstones recorded across separate transactions coalesce into one
    union that filters lazily decoded content — including an erased span
    that covers a record straddling block boundaries."""
    idx = DynamicIndex()
    w = Warren(idx)
    texts = {f"d{i}": (" ".join(f"w{i}_{j}" for j in range(600))
                       if i in (2, 3) else f"short doc {i} keyword")
             for i in range(8)}
    with w:
        w.transaction()
        for docid, text in texts.items():
            index_document(w, text, docid=docid)
        w.commit()
    # erase two ADJACENT docs (union must coalesce) + the big straddler
    spans = {}
    with w:
        for docid in ("d2", "d3", "d6"):
            lst = w.annotations("docid:" + docid)
            spans[docid] = (int(lst.starts[0]), int(lst.ends[0]))
    for docid in ("d2", "d3", "d6"):
        with w:
            w.transaction()
            w.erase(*spans[docid])
            w.commit()
    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    snap = idx.snapshot()
    # adjacent tombstones coalesced into one interval in the static union
    assert len(si.erased) == len(snap.erased)
    np.testing.assert_array_equal(si.erased.starts, snap.erased.starts)
    np.testing.assert_array_equal(si.erased.ends, snap.erased.ends)
    for docid, (p, q) in spans.items():
        assert si.translate(p, q) is None, docid
        assert len(si.annotations("docid:" + docid)) == 0
    # survivors read exactly, straight through the same blocks
    for docid in ("d0", "d1", "d4", "d5", "d7"):
        lst = si.annotations("docid:" + docid)
        assert si.translate(int(lst.starts[0]),
                            int(lst.ends[0])) == texts[docid]
    si.close()


def test_to_segment_materializes_lazy_content(tmp_path):
    """Promotion (going hot) is the one deliberately non-lazy read: the
    segment gets a RESIDENT content store bit-identical to lazy decode."""
    from repro.core.static import LazyContentStore
    from repro.core.txt import ContentStore

    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        for i in range(9):
            index_document(w, f"promote me {i} please", docid=f"d{i}")
        w.commit()
    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    seg = si.to_segment()
    assert isinstance(si.content, LazyContentStore)
    assert isinstance(seg.content, ContentStore)
    assert len(seg.content.records()) == len(si.content)
    for i, rec in enumerate(seg.content.records()):
        lazy = si.content.decode(i)
        assert (rec.lo, rec.hi, rec.text, rec.tokens) == \
            (lazy.lo, lazy.hi, lazy.text, lazy.tokens)
        np.testing.assert_array_equal(rec.offsets, lazy.offsets)
    si.close()


def test_lazy_content_store_refuses_writes(tmp_path):
    import pytest

    idx = DynamicIndex()
    w = Warren(idx)
    with w:
        w.transaction()
        index_document(w, "immutable content", docid="d0")
        w.commit()
    d = str(tmp_path / "static")
    write_static(idx, d)
    si = StaticIndex(d)
    with pytest.raises(TypeError):
        si.content.add(si.content.decode(0))
    si.close()
